#!/usr/bin/env python
"""OSU-style collective benchmark suite (BASELINE.md configs #1-#5).

Primary metric (the ONE printed JSON line, BASELINE.json config #3): bus
bandwidth of the framework's MPI_Allreduce path (coll/xla → ``lax.psum``
over the ICI mesh) at 16MB float32 vs raw hand-written ``jax.lax.psum`` —
``vs_baseline`` = framework / raw (north star ≥0.8 at ≥4MB).

Also runs (written to BENCH_SWEEP.json + BENCH_SWEEP.md, not the JSON
line):
  - allreduce latency + bus-bw sweep 8B→256MB (OSU osu_allreduce protocol)
  - bcast / allgather / reduce_scatter spot sizes (configs #4, #5)
  - persistent-collective (MPI_Allreduce_init analog) datapoint
  - 4-rank host-path ring smoke (config #1) when tpurun is runnable

Set OTPU_BENCH_FAST=1 to skip everything but the primary metric.
"""
import json
import os
import statistics
import subprocess
import sys
import time
import traceback

import numpy as np

from ompi_tpu.base.jaxenv import compile_cache_dir, require_tpu

# jax imports are DEFERRED into the functions that need them: the host
# modes (--serving, --history, ...) only launch CPU-pinned children, and
# a parent that never touches jax never holds the chip.

SWEEP_SIZES = (8, 4096, 262144, 4 << 20, 16 << 20, 64 << 20, 256 << 20)
SPOT_SIZES = (4096, 4 << 20, 64 << 20)
PRIMARY = 16 << 20


def _bus_factor(coll: str, ndev: int) -> float:
    # OSU bus-bandwidth conventions per collective
    if ndev <= 1:
        return 1.0
    if coll in ("allreduce",):
        return 2.0 * (ndev - 1) / ndev
    return (ndev - 1) / ndev


def _time_fn(fn, arg, iters=10, warmup=2):
    import jax

    for _ in range(warmup):
        out = fn(arg)
    jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(arg)
        jax.block_until_ready(out)
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


class DeviceBench:
    def __init__(self):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        self.devices = jax.devices()
        self.ndev = len(self.devices)
        self.mesh = jax.sharding.Mesh(np.array(self.devices), ("x",))
        self._P = P
        self._sm = shard_map

        import ompi_tpu
        from ompi_tpu.mca.coll.xla import XlaCollModule

        self.world = ompi_tpu.init()
        self.xla_mod = next(
            (m for m in self.world.coll_modules
             if isinstance(m, XlaCollModule)), None)
        if self.xla_mod is None:
            raise RuntimeError("coll/xla did not select on COMM_WORLD")

    def make(self, nbytes_per_rank: int):
        nelem = max(1, nbytes_per_rank // 4)
        return self.xla_mod.make_world_array(
            np.ones((self.world.size, nelem), np.float32))

    def raw_fn(self, coll: str):
        """Raw-XLA twin of each framework path, pinned to the IDENTICAL
        algorithm/program shape (a different shape makes the ratio
        meaningless as a dispatch-overhead guard — an earlier bcast
        baseline gathered n blocks to deliver one and made the
        framework look 1.5x 'faster')."""
        import jax
        import jax.numpy as jnp

        P, sm = self._P, self._sm
        n = self.ndev

        def bcast_body(t):   # the same two-regime selection as
            me = jax.lax.axis_index("x")     # xla.py bcast_array
            nbytes_payload = int(np.prod(t.shape[1:])) * t.dtype.itemsize
            if nbytes_payload >= (256 << 10):   # scatter+allgather
                contrib = jnp.where(me == 0, t[0], jnp.zeros_like(t[0]))
                flat = contrib.reshape(-1)
                blk = -(-flat.shape[0] // n)
                if blk * n != flat.shape[0]:
                    flat = jnp.pad(flat, (0, blk * n - flat.shape[0]))
                part = jax.lax.psum_scatter(flat.reshape(n, blk), "x",
                                            scatter_dimension=0,
                                            tiled=False)
                full = jax.lax.all_gather(part, "x")
                return full.reshape(-1)[:t[0].size].reshape(t.shape)
            rel = me % n
            cur = t
            k = 1
            while k < n:
                perm = [(i, i + k) for i in range(min(k, n - k))]
                recvd = jax.lax.ppermute(cur, "x", perm)
                newly = (rel >= k) & (rel < 2 * k)
                cur = jnp.where(newly, recvd, cur)
                k *= 2
            return cur

        bodies = {
            "allreduce": lambda t: jax.lax.psum(t[0], "x"),
            "bcast": bcast_body,
            "allgather": lambda t: jax.lax.all_gather(t[0], "x"),
        }
        out_specs = {"allreduce": P(), "bcast": P("x"), "allgather": P()}
        if coll == "reduce_scatter":
            def body(t):  # (1, n*S) -> (1, S)
                return jax.lax.psum_scatter(
                    t[0].reshape(self.ndev, -1), "x",
                    scatter_dimension=0, tiled=False)[None]
            return jax.jit(sm(body, mesh=self.mesh, in_specs=P("x"),
                              out_specs=P("x"), check_vma=False))
        return jax.jit(sm(bodies[coll], mesh=self.mesh, in_specs=P("x"),
                          out_specs=out_specs[coll], check_vma=False))

    def fw_fn(self, coll: str):
        w = self.world
        if coll == "reduce_scatter":
            # framework reduce_scatter wants (n, n, *S)
            return lambda x: w.reduce_scatter_array(x)
        return {
            "allreduce": lambda x: w.allreduce_array(x),
            "bcast": lambda x: w.bcast_array(x),
            "allgather": lambda x: w.allgather_array(x),
        }[coll]

    def _timed_pair(self, coll: str, fw, raw, x, xr, nbytes: int,
                    iters: int) -> dict:
        """ONE measurement protocol for every row: warmup, interleaved
        fw/raw samples (clock drift hits both sides of a pair equally),
        medians + median pairwise ratio.  Shared so no row can drift
        onto a skewed protocol again (round 2's 'persistent slower than
        one-shot' artifact was exactly that)."""
        import jax

        out = fw(x)
        out2 = raw(xr)
        jax.block_until_ready((out, out2))   # compile round
        out = fw(x)
        out2 = raw(xr)
        jax.block_until_ready((out, out2))   # steady-state warmup pair
        fw_s, raw_s = [], []
        for i in range(iters):
            # alternate which side goes first: a fixed order would hand
            # any second-call advantage to one side systematically
            # (suspected in round 2's allgather-4MB 0.609 — fw and raw
            # compile to byte-identical programs there)
            first, second = (fw, raw) if i % 2 == 0 else (raw, fw)
            xa, xb = (x, xr) if i % 2 == 0 else (xr, x)
            t0 = time.perf_counter()
            jax.block_until_ready(first(xa))
            t1 = time.perf_counter()
            jax.block_until_ready(second(xb))
            t2 = time.perf_counter()
            if i % 2 == 0:
                fw_s.append(t1 - t0)
                raw_s.append(t2 - t1)
            else:
                raw_s.append(t1 - t0)
                fw_s.append(t2 - t1)
        fw_t, raw_t = statistics.median(fw_s), statistics.median(raw_s)
        pair_ratio = statistics.median(r / f_ for f_, r in zip(fw_s, raw_s))
        f = _bus_factor(coll.split("_")[0], self.ndev)
        return {
            "coll": coll, "nbytes": nbytes,
            "fw_lat_us": round(fw_t * 1e6, 2),
            "raw_lat_us": round(raw_t * 1e6, 2),
            "fw_bw_gbs": round(f * nbytes / fw_t / 1e9, 3),
            "raw_bw_gbs": round(f * nbytes / raw_t / 1e9, 3),
            "ratio": round(pair_ratio, 4),
        }

    def point(self, coll: str, nbytes: int, iters: int = 10) -> dict:
        if coll == "reduce_scatter":
            # (n, n, S): each rank contributes n blocks of nbytes/n
            nelem = max(self.ndev, nbytes // 4 // self.ndev * self.ndev)
            x = self.xla_mod.make_world_array(np.ones(
                (self.world.size, self.ndev, nelem // self.ndev),
                np.float32))
            xr = self.make(nbytes)
        else:
            x = xr = self.make(nbytes)
        return self._timed_pair(coll, self.fw_fn(coll), self.raw_fn(coll),
                                x, xr, nbytes, iters)

    def persistent_point(self, nbytes: int, iters: int = 40) -> dict:
        """MPI_Allreduce_init analog, measured by the same interleaved
        protocol as every other row."""
        x = self.make(nbytes)
        h = self.world.allreduce_array_init(x)
        return self._timed_pair("allreduce_persistent", h,
                                self.raw_fn("allreduce"), x, x, nbytes,
                                iters)


#: bf16 peak FLOP/s by device_kind substring (public TPU specs)
_CHIP_PEAK_BF16 = (
    ("v6", 918e12), ("trillium", 918e12), ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def _chip_peak_flops(device_kind: str) -> float:
    """bf16 peak of a TPU ``device_kind``.  A kind the table does not
    know is an error, not ``mfu: null``: a utilisation against a
    guessed peak is worse than none."""
    kind = (device_kind or "").lower()
    for pat, bf16 in _CHIP_PEAK_BF16:
        if pat in kind:
            return bf16
    raise RuntimeError(
        f"no peak FLOP/s known for TPU device_kind {device_kind!r}: add "
        "it to _CHIP_PEAK_BF16 with its source")


def _attempt(failed: list, what: str, fn):
    """Run one device row.  A row that raises is reported with its
    traceback and recorded in ``failed``; the run goes on only so that
    rows already measured are flushed, and then exits non-zero."""
    try:
        return fn()
    except Exception:
        print(f"bench: device row {what} FAILED:", file=sys.stderr)
        traceback.print_exc()
        failed.append(what)
        return None


def mfu_rows(failed: list) -> list:
    """Single-chip MFU rows — achieved FLOP/s ÷ chip peak for (a) the
    flagship train step (``__graft_entry__.entry``), (b) the pallas
    flash-attention block kernel vs its jnp twin, (c) the MXU matmul
    the fused GEMM-overlap kernel builds on.  The op/avx discipline
    (``ompi/mca/op/avx/op_avx_functions.c``): keep the math at hardware
    peak, and measure that claim.  Train-step FLOPs come from XLA's
    cost analysis (not hand math); the pallas kernel's inner FLOPs are
    invisible to XLA and use the closed-form attention count.  TPU
    only; a section that raises lands in ``failed``.
    """
    import jax
    import jax.numpy as jnp

    kind = require_tpu("bench")[0].device_kind
    peak = _chip_peak_flops(kind)
    rows = []

    def row(name, flops, secs, extra=None):
        achieved = flops / secs
        r = {"metric": name, "grade": "device", "device_kind": kind,
             "tflops": round(achieved / 1e12, 3),
             "model_flops": int(flops),
             "lat_us": round(secs * 1e6, 1),
             "mfu": round(achieved / peak, 4),
             "peak_tflops_assumed": round(peak / 1e12, 1)}
        if extra:
            r.update(extra)
        rows.append(r)
        return r

    def train_step_rows():
        # (a) flagship train step at bench scale: same program as the
        # driver contract (__graft_entry__.entry -> parallel.dryrun),
        # with OTPU_MODEL_SCALE raising the width/seq dims to
        # MXU-saturating sizes — tracing-scale shapes would measure
        # dispatch, not FLOPs
        old_scale = os.environ.get("OTPU_MODEL_SCALE")
        try:
            os.environ["OTPU_MODEL_SCALE"] = os.environ.get(
                "OTPU_BENCH_MODEL_SCALE", "64")
            scale = int(os.environ["OTPU_MODEL_SCALE"])
            from ompi_tpu.parallel.dryrun import make_step_and_args

            fn, example_args, _ = make_step_and_args(jax.devices()[:1])
            jfn = jax.jit(fn)
            ca = jfn.lower(*example_args).compile().cost_analysis()
            t = _time_fn(lambda a: jfn(*a), example_args, iters=10)
            # f32 params, but JAX default matmul precision runs one bf16
            # MXU pass per f32 matmul on TPU — bf16 peak is the roofline
            row("mfu_train_step", float(ca["flops"]), t,
                extra={"model_scale": scale,
                       "matmul_precision": "default (bf16 MXU passes)"})
            # the bf16 compute-dtype mode (half-width activations,
            # per-block param casts): the achievable-MFU row for
            # production configs
            from ompi_tpu.base.var import registry as _reg

            _cd = _reg.lookup("otpu_parallel_compute_dtype")
            _old_cd = _cd.value
            try:
                _cd.set("bfloat16")
                fnb, args_b, _ = make_step_and_args(jax.devices()[:1])
                jfnb = jax.jit(fnb)
                cab = jfnb.lower(*args_b).compile().cost_analysis()
                tb = _time_fn(lambda a: jfnb(*a), args_b, iters=10)
                row("mfu_train_step_bf16", float(cab["flops"]), tb,
                    extra={"model_scale": scale,
                           "vs_f32_speedup": round(t / tb, 3)})
            finally:
                _cd.set(_old_cd)
        finally:
            if old_scale is None:
                os.environ.pop("OTPU_MODEL_SCALE", None)
            else:
                os.environ["OTPU_MODEL_SCALE"] = old_scale

    def flash_rows():
        # (b) flash-attention block kernel vs the jnp twin it replaces
        from ompi_tpu.ops import flash_attention as fa

        b_, h, sq, skv, d = 4, 8, 2048, 2048, 128
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b_, h, sq, d), jnp.bfloat16)
        k = jax.random.normal(key, (b_, h, skv, d), jnp.bfloat16)
        v = jax.random.normal(key, (b_, h, skv, d), jnp.bfloat16)
        m0 = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
        num0 = jnp.zeros(q.shape, jnp.float32)
        den0 = jnp.zeros(q.shape[:-1], jnp.float32)
        # 2 MXU matmuls (qk^T, pv): 2 * 2*sq*skv*d each, per (b, h)
        flops = 4.0 * b_ * h * sq * skv * d
        flash = jax.jit(lambda a: fa.flash_block_update(*a))
        t_flash = _time_fn(flash, (q, k, v, m0, num0, den0), iters=10)
        jnp_twin = jax.jit(lambda a: fa._update_jnp(*a))
        t_jnp = _time_fn(jnp_twin, (q, k, v, m0, num0, den0), iters=10)
        row("mfu_flash_attention", flops, t_flash,
            extra={"vs_jnp_speedup": round(t_jnp / t_flash, 3)})
        # causal variant: same kernel + fused additive bias; ~half the
        # scores are masked so model FLOPs halve (the MXU still runs
        # the full tiles — mfu reflects achieved useful FLOPs)
        bias = jnp.where(jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :],
                         0.0, -jnp.inf).astype(jnp.float32)
        flash_c = jax.jit(lambda a: fa.flash_block_update_biased(*a))
        t_c = _time_fn(flash_c, (q, k, v, m0, num0, den0, bias),
                       iters=10)
        row("mfu_flash_attention_causal", flops / 2.0, t_c,
            extra={"vs_dense_flash": round(t_flash / t_c, 3)})

    def matmul_row():
        # (c) the MXU phase of the fused GEMM-overlap kernel: a plain
        # bf16 matmul at benchmark size is its compute roofline
        mm = 4096
        a = jnp.ones((mm, mm), jnp.bfloat16)
        bmat = jnp.ones((mm, mm), jnp.bfloat16)
        f = jax.jit(lambda ab: ab[0] @ ab[1])
        t = _time_fn(f, (a, bmat), iters=10)
        row("mfu_matmul_bf16", 2.0 * mm ** 3, t, extra={"dim": mm})

    _attempt(failed, "mfu_train_step", train_step_rows)
    _attempt(failed, "mfu_flash_attention", flash_rows)
    _attempt(failed, "mfu_matmul_bf16", matmul_row)
    return rows


def host_ring_smoke() -> dict:
    """BASELINE config #1: 4-rank ring over the host path (tpurun)."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "4",
         sys.executable, os.path.join(here, "examples", "ring.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    dt = time.perf_counter() - t0
    return {"coll": "ring_4rank_host", "ok": proc.returncode == 0,
            "wall_s": round(dt, 2)}


_HOST_OSU = """
import json, statistics, sys, time
import numpy as np
import ompi_tpu

w = ompi_tpu.init()
out = []
for nbytes in (4096, 262144, 4 << 20):
    x = np.ones(nbytes // 4, np.float32)
    for _ in range(3):
        w.allreduce(x)
    lat = []
    iters = 20 if nbytes <= 262144 else 8
    for _ in range(iters):
        w.barrier()
        t0 = time.perf_counter()
        w.allreduce(x)
        lat.append(time.perf_counter() - t0)
    out.append((nbytes, statistics.median(lat)))
if w.rank == 0:
    print("OSU_HOST " + json.dumps(out))
ompi_tpu.finalize()
"""


def host_allreduce_points(n: int = 4) -> list:
    """BASELINE config #2: OSU allreduce over the host path (pml/sm +
    coll/tuned ladder), n CPU ranks under tpurun."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_HOST_OSU)
        script = f.name
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", str(n),
             sys.executable, script],
            capture_output=True, text=True, timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        line = next((ln for ln in proc.stdout.splitlines()
                     if "OSU_HOST" in ln), None)
        if proc.returncode or line is None:
            print(f"host allreduce bench failed (rc={proc.returncode}):\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            return [{"coll": "allreduce_host_tuned", "ok": False}]
        pts = _json.loads(line.split("OSU_HOST ", 1)[1])
        f_bus = _bus_factor("allreduce", n)
        return [{"coll": "allreduce_host_tuned", "nbytes": nb,
                 "fw_lat_us": round(t * 1e6, 1),
                 "fw_bw_gbs": round(f_bus * nb / t / 1e9, 4)}
                for nb, t in pts]
    finally:
        os.unlink(script)


_RGET_BW = """
import json, statistics, sys, time
import numpy as np
import ompi_tpu

w = ompi_tpu.init()
out = []
WINDOW = 4
for nbytes in (4 << 20, 16 << 20):
    x = np.ones(nbytes, np.uint8)
    bufs = [np.empty_like(x) for _ in range(WINDOW)]
    ack = np.zeros(1, np.float64)
    def once():
        if w.rank == 0:
            reqs = [w.isend(x, dest=1, tag=9) for _ in range(WINDOW)]
            for r in reqs:
                r.wait()
            w.recv(ack, source=1, tag=10)
        else:
            reqs = [w.irecv(bufs[i], source=0, tag=9)
                    for i in range(WINDOW)]
            for r in reqs:
                r.wait()
            w.send(ack, dest=0, tag=10)
    for _ in range(2):
        once()
    iters = 6 if nbytes <= (4 << 20) else 4
    ts = []
    for _ in range(iters):
        w.barrier()
        t0 = time.perf_counter()
        once()
        ts.append(time.perf_counter() - t0)
    t = statistics.median(ts)
    out.append((nbytes, WINDOW * nbytes / t / 1e9))
if w.rank == 0:
    print("RGET_BW " + json.dumps(out))
ompi_tpu.finalize()
"""


def host_rget_points() -> list:
    """RGET-vs-FRAG isolation rows (pml_ob1_sendreq.h:375-401): 2-rank
    OSU-style pt2pt bandwidth at 4MB/16MB over btl/sm (true one-sided
    segment pull) and btl/tcp via --fake-nodes (pull emulation), each
    measured with the RGET protocol forced ON (rget_limit 512k) and OFF
    (rget_limit 0 -> RNDV FRAG stream).  Striping is disabled so ONE
    transport carries the message and the protocol delta is isolated."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_RGET_BW)
        script = f.name
    rows = []
    try:
        bw = {}   # (transport, proto) -> {nbytes: GB/s}
        for transport in ("sm", "tcp"):
            for proto, limit in (("rget", "512k"), ("frag", "0")):
                cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun",
                       "-n", "2",
                       "--mca", "pml_ob1_rget_limit", limit,
                       "--mca", "pml_ob1_stripe", "0"]
                if transport == "tcp":
                    # emulation is gated off by default (measured slower
                    # than FRAG); force it so the row keeps documenting
                    # the crossover
                    cmd += ["--fake-nodes", "2",
                            "--mca", "pml_ob1_rget_emulate", "1"]
                cmd += [sys.executable, script]
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=300,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"))
                line = next((ln for ln in proc.stdout.splitlines()
                             if "RGET_BW" in ln), None)
                if proc.returncode or line is None:
                    print(f"rget bench ({transport},{proto}) failed "
                          f"(rc={proc.returncode}):\n"
                          f"{proc.stderr[-1500:]}", file=sys.stderr)
                    continue
                pts = _json.loads(line.split("RGET_BW ", 1)[1])
                bw[(transport, proto)] = {nb: g for nb, g in pts}
                rows.extend(
                    {"coll": f"pt2pt_{transport}_{proto}", "nbytes": nb,
                     "fw_bw_gbs": round(g, 4)} for nb, g in pts)
        for transport in ("sm", "tcp"):
            r_on = bw.get((transport, "rget"), {})
            r_off = bw.get((transport, "frag"), {})
            rows.extend(
                {"coll": f"rget_speedup_{transport}", "nbytes": nb,
                 "ratio": round(r_on[nb] / r_off[nb], 3)}
                for nb in r_on if r_off.get(nb))
    finally:
        os.unlink(script)
    return rows


_PART_PP = """
import json, statistics, sys, time
import numpy as np
import ompi_tpu

w = ompi_tpu.init()
out = []
for nbytes, parts in ((65536, 4), (1 << 20, 4), (1 << 20, 16)):
    n = nbytes // 8
    x = np.ones(n, np.float64)
    y = np.empty(n, np.float64)
    if w.rank == 0:
        s = w.psend_init(x, parts, dest=1, tag=5)
        r = w.precv_init(y, parts, source=1, tag=6)
    else:
        r = w.precv_init(y, parts, source=0, tag=5)
        s = w.psend_init(x, parts, dest=0, tag=6)
    def once():
        if w.rank == 0:
            s.start()
            for p in range(parts):
                s.pready(p)
            s.wait()
            r.start(); r.wait()
        else:
            r.start(); r.wait()
            s.start()
            for p in range(parts):
                s.pready(p)
            s.wait()
    for _ in range(3):
        once()
    iters = 20 if nbytes <= 65536 else 8
    lat = []
    for _ in range(iters):
        w.barrier()
        t0 = time.perf_counter()
        once()
        lat.append(time.perf_counter() - t0)
    out.append((nbytes, parts, statistics.median(lat)))
if w.rank == 0:
    print("PART_PP " + json.dumps(out))
ompi_tpu.finalize()
"""


def host_part_points() -> list:
    """MPI-4 partitioned ping-pong (mca/part/persist over pml/sm):
    message size x partition count, full round trip per iteration.  The
    partitions-vs-latency delta is the per-Pready framing cost; the
    same size at 4 vs 16 partitions bounds the aggregation overhead."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_PART_PP)
        script = f.name
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "2",
             sys.executable, script],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        line = next((ln for ln in proc.stdout.splitlines()
                     if "PART_PP" in ln), None)
        if proc.returncode or line is None:
            print(f"partitioned pingpong bench failed "
                  f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}",
                  file=sys.stderr)
            return [{"coll": "part_pingpong", "ok": False}]
        pts = _json.loads(line.split("PART_PP ", 1)[1])
        # round trip moves nbytes each way: bandwidth = 2*nbytes/t
        return [{"coll": f"part_pingpong_{parts}p", "nbytes": nb,
                 "fw_lat_us": round(t * 1e6, 1),
                 "fw_bw_gbs": round(2 * nb / t / 1e9, 4)}
                for nb, parts, t in pts]
    finally:
        os.unlink(script)


_SERVING = """
import json, sys
import ompi_tpu
from ompi_tpu.serving import ContinuousBatchScheduler, Router, ShardWorker
from ompi_tpu.serving.driver import PoissonDriver

mode = sys.argv[1]
w = ompi_tpu.init()
if w.rank == 0:
    sched = ContinuousBatchScheduler(max_batch=8,
                                     max_batch_tokens=1 << 14, slots=8)
    r = Router(w, scheduler=sched, stages=(mode == "stages"),
               decode_chunk=4, kv_elems=256)
    rep = PoissonDriver(rate_rps=300.0, n_requests=96,
                        prompt_lens=(8, 64), decode_lens=(4, 24),
                        seed=5).run(r, max_wall_s=150)
    r.shutdown()
    print("SERVING " + json.dumps(rep), flush=True)
elif mode == "stages" and w.rank == 1:
    ShardWorker(w, router=0, role="prefill", peer=2, slots=8,
                kv_elems=256).serve()
elif mode == "stages" and w.rank == 2:
    ShardWorker(w, router=0, role="decode", peer=1, slots=8,
                kv_elems=256, kv_partitions=16).serve()
else:
    ShardWorker(w, router=0).serve()
ompi_tpu.finalize()
"""


def serving_rows() -> list:
    """The heavy-traffic serving benchmark (ROADMAP item 3): a Poisson
    open-loop driver against the continuous-batching engine — router +
    2 workers, colocated AND disaggregated (KV slabs over partitioned
    requests) — reporting p50/p99 request latency from the otpu-trace
    log2 histograms and decoded tokens/sec.  A queueing benchmark, not
    a ping-pong: latency includes admission waiting, which is why it is
    a new surface next to the OSU-style sweeps."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_SERVING)
        script = f.name
    rows = []
    try:
        for mode in ("colocated", "stages"):
            with tempfile.TemporaryDirectory() as td:
                proc = subprocess.run(
                    [sys.executable, "-m", "ompi_tpu.tools.tpurun",
                     "-n", "3",
                     "--mca", "otpu_trace_enable", "1",
                     "--mca", "otpu_trace_requests", "1",
                     "--mca", "otpu_trace_dir", td,
                     sys.executable, script, mode],
                    capture_output=True, text=True, timeout=300,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"))
                line = next((ln for ln in proc.stdout.splitlines()
                             if "SERVING " in ln), None)
                if proc.returncode or line is None:
                    print(f"serving bench ({mode}) failed "
                          f"(rc={proc.returncode}):\n"
                          f"{proc.stderr[-2000:]}",
                          file=sys.stderr)
                    rows.append({"coll": f"serving_poisson_{mode}",
                                 "ok": False})
                    continue
                rep = _json.loads(line.split("SERVING ", 1)[1])
                row = {
                    "coll": f"serving_poisson_{mode}",
                    "nbytes": rep["requests"],
                    "p50_ms": rep["p50_ms"], "p99_ms": rep["p99_ms"],
                    "p99_exact_ms": rep["p99_exact_ms"],
                    "tokens_per_s": rep["tokens_per_s"],
                    "req_per_s": rep["req_per_s"],
                }
                row.update(_req_stage_medians(td))
                rows.append(row)
    finally:
        os.unlink(script)
    return rows


_FLEET = """
import json, sys
import ompi_tpu
from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                              ShardWorker)

w = ompi_tpu.init()
if w.rank == 0:
    fleet = FleetController(w, tenants={"ten_a": 2, "ten_b": 1})
    drv = MixedPoissonDriver({
        "ten_a": dict(model="m_a", rate_rps=300.0, n_requests=48,
                      prompt_lens=(8, 64), decode_lens=(4, 24),
                      prefixes=3, prefix_len=32),
        "ten_b": dict(model="m_b", rate_rps=200.0, n_requests=32,
                      prompt_lens=(8, 64), decode_lens=(4, 24),
                      prefixes=2, prefix_len=16),
    }, seed=5)
    rep = drv.run(fleet, max_wall_s=150)
    fleet.shutdown()
    print("FLEET " + json.dumps(rep), flush=True)
else:
    ShardWorker(w, router=0).serve()
ompi_tpu.finalize()
"""


def fleet_rows() -> list:
    """``bench.py --serving``'s fleet half: TWO model pools + TWO
    weighted tenants under the mixed-workload driver (shared prompt
    prefixes included, so the per-tenant numbers reflect prefix-aware
    routing).  One row per tenant — the per-tenant p99 IS the fleet's
    contract number (a blended percentile would hide one tenant
    starving) — plus the prefix-cache hit rate on each row."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_FLEET)
        script = f.name
    rows = []
    try:
        with tempfile.TemporaryDirectory() as td:
            proc = subprocess.run(
                [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "5",
                 "--pool", "m_a:1,2", "--pool", "m_b:3,4",
                 "--mca", "otpu_trace_enable", "1",
                 "--mca", "otpu_trace_requests", "1",
                 "--mca", "otpu_trace_dir", td,
                 sys.executable, script],
                capture_output=True, text=True, timeout=300,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            line = next((ln for ln in proc.stdout.splitlines()
                         if "FLEET " in ln), None)
            if proc.returncode or line is None:
                print(f"fleet bench failed (rc={proc.returncode}):\n"
                      f"{proc.stderr[-2000:]}", file=sys.stderr)
                return [{"coll": "serving_fleet", "ok": False}]
            rep = _json.loads(line.split("FLEET ", 1)[1])
            stages = _req_stage_medians(td)
            for name, tr in sorted(rep["tenants"].items()):
                row = {
                    "coll": f"serving_fleet_{name}",
                    "nbytes": tr["requests"],
                    "p50_ms": tr["p50_ms"], "p99_ms": tr["p99_ms"],
                    "p99_exact_ms": tr["p99_exact_ms"],
                    "tokens_per_s": tr["tokens_per_s"],
                    "req_per_s": round(tr["requests"]
                                       / rep["elapsed_s"], 1),
                    "prefix_hit_rate": rep["prefix_hit_rate"],
                }
                # the fleet trace is one merged timeline over both
                # pools — the stage decomposition is fleet-wide, so
                # every tenant row carries the same medians
                row.update(stages)
                rows.append(row)
    finally:
        os.unlink(script)
    return rows


_SPEC = """
import json, sys, time
import ompi_tpu
from ompi_tpu.serving import Router, ShardWorker

k = int(sys.argv[1])
w = ompi_tpu.init()
if w.rank == 0:
    r = Router(w, workers=[1, 2], decode_chunk=8)
    # closed-loop saturation: every request is in the queue before the
    # first tick, so tokens/sec measures the decode engine, not the
    # arrival process (the open-loop Poisson rows are arrival-limited
    # and would read a multiplier of ~1.0 no matter what decode does)
    for i in range(16):
        r.submit(8, 32, rid=2000 + i, tenant="bench")
    t0 = time.perf_counter()
    done = r.serve_until_drained(max_ticks=200000)
    dt = time.perf_counter() - t0
    toks = sum(len(q.tokens) for q in done)
    assert len(done) == 16, len(done)
    r.shutdown()
    print("SPEC " + json.dumps(
        {"k": k, "tokens": toks, "elapsed_s": round(dt, 4),
         "tokens_per_s": round(toks / dt, 1)}), flush=True)
else:
    ShardWorker(w, router=0, spec_k=k).serve()
ompi_tpu.finalize()
"""

_OVERLOAD = """
import json
import ompi_tpu
from ompi_tpu.base.var import registry
from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                              ShardWorker)

w = ompi_tpu.init()
if w.rank == 0:
    registry.set("otpu_serving_slo_p99_ms", 800.0)
    fleet = FleetController(
        w, tenants={"int": 2, "bat": 1},
        autoscale=dict(poll_ticks=10**9, idle_patience=10**9),
        frontdoor=dict(queue_cap=6, backlog=3, retry_s=0.01,
                       hold_ticks=20, window=16))
    drv = MixedPoissonDriver({
        "int": dict(model="m_a", rate_rps=150, n_requests=28,
                    prompt_lens=(4, 8), decode_lens=(2, 4),
                    slo="interactive"),
        "bat": dict(model="m_a", rate_rps=400, n_requests=36,
                    prompt_lens=(4, 8), decode_lens=(6, 12),
                    slo="batch"),
    }, seed=13)
    rep = drv.run(fleet, max_wall_s=180, check_invariants=True)
    st = fleet.frontdoor.stats()
    fleet.shutdown()
    cls = rep["slo_classes"]
    print("OVERLOAD " + json.dumps(
        {"requests": rep["requests"], "elapsed_s": rep["elapsed_s"],
         "shed": rep["shed"], "retried": rep["retried"],
         "preempts": st["preempts"], "classes": cls}), flush=True)
else:
    ShardWorker(w, router=0).serve()
ompi_tpu.finalize()
"""


def frontdoor_rows() -> list:
    """``bench.py --serving``'s front-door half (ROADMAP item 5):

    * ``serving_spec_k{0,4}``: the speculative-decoding A/B — the SAME
      closed-loop saturated workload on the SAME 2 chips, plain decode
      vs draft-propose/target-verify, plus the derived
      ``serving_spec_multiplier`` row (tokens/sec ratio; the pin says
      it must stay > 1 or speculation is a loss);
    * ``serving_overload_{interactive,batch}``: the sustained-overload
      contract — MixedPoissonDriver above pool capacity through the
      armed door, per-class exact p99 and the shed/retry ledger.

    Every row carries ``fd: True`` so the Poisson table renderer can
    route it to the front-door subsection."""
    import json as _json
    import subprocess
    import tempfile

    rows = []
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_SPEC)
        script = f.name
    reps = {}
    try:
        for k in (0, 4):
            proc = subprocess.run(
                [sys.executable, "-m", "ompi_tpu.tools.tpurun",
                 "-n", "3", sys.executable, script, str(k)],
                capture_output=True, text=True, timeout=300,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            line = next((ln for ln in proc.stdout.splitlines()
                         if "SPEC " in ln), None)
            if proc.returncode or line is None:
                print(f"spec bench (k={k}) failed "
                      f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}",
                      file=sys.stderr)
                rows.append({"coll": f"serving_spec_k{k}", "fd": True,
                             "ok": False})
                continue
            rep = _json.loads(line.split("SPEC ", 1)[1])
            reps[k] = rep
            rows.append({"coll": f"serving_spec_k{k}", "fd": True,
                         "nbytes": rep["tokens"],
                         "tokens_per_s": rep["tokens_per_s"],
                         "elapsed_s": rep["elapsed_s"]})
    finally:
        os.unlink(script)
    if 0 in reps and 4 in reps:
        mult = reps[4]["tokens_per_s"] / reps[0]["tokens_per_s"]
        rows.append({"coll": "serving_spec_multiplier", "fd": True,
                     "nbytes": reps[4]["tokens"],
                     "multiplier": round(mult, 2)})
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_OVERLOAD)
        script = f.name
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
             "--pool", "m_a:1,2", sys.executable, script],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        line = next((ln for ln in proc.stdout.splitlines()
                     if "OVERLOAD " in ln), None)
        if proc.returncode or line is None:
            print(f"overload bench failed (rc={proc.returncode}):\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            rows.append({"coll": "serving_overload", "fd": True,
                         "ok": False})
            return rows
        rep = _json.loads(line.split("OVERLOAD ", 1)[1])
        total = rep["requests"] + rep["shed"]
        for cls in ("interactive", "batch"):
            c = rep["classes"].get(cls)
            if c is None:
                continue
            rows.append({
                "coll": f"serving_overload_{cls}", "fd": True,
                "nbytes": c["requests"],
                "p50_ms": c["p50_ms"],
                "p99_exact_ms": c["p99_exact_ms"],
                "shed": c["shed"], "retried": c["retried"],
                "shed_rate": round(rep["shed"] / total, 4),
                "preempts": rep["preempts"],
            })
    finally:
        os.unlink(script)
    return rows


def _frontdoor_md_lines(fd_rows) -> list:
    lines = ["", "### Front door (overload shedding + speculative "
             "decode)", "",
             "`serving_spec_k*` is the closed-loop saturation A/B at "
             "matched chips (router + 2 workers, 16 requests queued "
             "up-front): plain decode pays one target pass per token, "
             "speculative decode verifies a k-token draft window per "
             "target pass — `serving_spec_multiplier` is the "
             "tokens/sec ratio and must stay > 1. "
             "`serving_overload_*` rows drive Poisson arrivals above "
             "pool capacity through the armed front door "
             "(`otpu_serving_slo_p99_ms` 800): per-SLO-class exact "
             "p99, requests shed at the door (each re-arrived after "
             "its retry-after), and batch preemptions.", "",
             "| row | n | tokens/s | mult | p50 ms | p99 exact ms | "
             "shed | retried | shed rate | preempts |",
             "|---|---|---|---|---|---|---|---|---|---|"]

    def _c(r, key, fmt="{}"):
        v = r.get(key)
        return fmt.format(v) if v is not None else "-"

    for r in fd_rows:
        if not r.get("ok", True):
            lines.append(f"| {r['coll']} | FAILED | - | - | - | - | - "
                         "| - | - | - |")
            continue
        lines.append(
            f"| {r['coll']} | {r.get('nbytes', '-')} | "
            f"{_c(r, 'tokens_per_s')} | {_c(r, 'multiplier')} | "
            f"{_c(r, 'p50_ms')} | {_c(r, 'p99_exact_ms')} | "
            f"{_c(r, 'shed')} | {_c(r, 'retried')} | "
            f"{_c(r, 'shed_rate')} | {_c(r, 'preempts')} |")
    return lines


def _req_stage_medians(trace_dir: str) -> dict:
    """Per-request stage medians from the per-rank traces a
    request-armed (``otpu_trace_requests``) serving run exported —
    the REAL ``otpu_analyze --requests`` decomposition over the
    merged timeline, not a shadow estimator in the bench script.
    Empty dict when the run produced no decomposable requests (the
    row simply doesn't grow the column; the pin test treats that as
    a regression)."""
    from ompi_tpu.tools import otpu_analyze
    try:
        events = otpu_analyze.load_events([trace_dir])
    except (SystemExit, OSError, ValueError):
        return {}
    rep = otpu_analyze.requests_report(events)
    med = rep.get("stage_median_us") or {}
    if not med:
        return {}
    return {"stage_median_ms": {s: round(v / 1000.0, 3)
                                for s, v in med.items()},
            "req_decomposed": int(rep.get("decomposed", 0))}


def _stage_cell(r: dict) -> str:
    """Compact q/d/p/k/dec/str stage-median cell for the md table
    (absent stages — e.g. prefill/kv on a colocated row whose engine
    prefills inline — render as '-')."""
    from ompi_tpu.tools.otpu_analyze import REQ_STAGES
    med = r.get("stage_median_ms")
    if not med:
        return "-"
    return "/".join(f"{med[s]:g}" if s in med else "-"
                    for s in REQ_STAGES)


def _serving_md_section(rows) -> list:
    # front-door rows (speculative A/B, overload contract) carry a
    # different column set — route them to their own subsection instead
    # of KeyError-ing on p50_ms/p99_ms below
    fd_rows = [r for r in rows if r.get("fd")]
    rows = [r for r in rows if not r.get("fd")]
    lines = ["", "## Serving (Poisson open-loop, router + 2 workers)",
             "",
             "Request latency percentiles come from the otpu-trace "
             "log2 histogram estimator (`p99_exact` is the driver's "
             "own sample check); tokens/sec counts decoded tokens. "
             "Open-loop queueing numbers, not ping-pong latency. "
             "`serving_fleet_*` rows are PER TENANT from the two-pool "
             "/ two-tenant fleet run (weighted fair-share admission, "
             "prefix-aware routing — `pfx%` is the cache hit rate). "
             "`stage med ms` is the otpu-req per-request decomposition "
             "(queue/dispatch/prefill/kv/decode/stream medians from "
             "`otpu_analyze --requests` over the run's merged "
             "timeline; fleet rows share one fleet-wide cell).",
             "",
             "| mode | requests | p50 ms | p99 ms | p99 exact ms | "
             "tokens/s | req/s | pfx% | stage med ms (q/d/p/k/dec/str) |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if not r.get("ok", True):
            lines.append(f"| {r['coll']} | FAILED | - | - | - | - | "
                         "- | - | - |")
            continue
        pfx = r.get("prefix_hit_rate")
        pfx_s = f"{100.0 * pfx:.0f}%" if pfx is not None else "-"
        lines.append(
            f"| {r['coll']} | {r['nbytes']} | {r['p50_ms']} | "
            f"{r['p99_ms']} | {r['p99_exact_ms']} | "
            f"{r['tokens_per_s']} | {r['req_per_s']} | {pfx_s} | "
            f"{_stage_cell(r)} |")
    if fd_rows:
        lines += _frontdoor_md_lines(fd_rows)
    return lines


def _splice_md_section(md: str, heading_prefix: str,
                       new_lines: list) -> str:
    """Replace ONE '## ' section of the sweep markdown (matched by its
    heading prefix; appended at the end when absent), PRESERVING every
    later section — a plain partition-and-truncate silently deleted
    whatever another refresher had appended after the replaced heading
    (the --serving run ate the committed Recovery/Quant sections)."""
    head, sep, tail = md.partition("\n" + heading_prefix)
    rest = ""
    if sep:
        nxt = tail.find("\n## ")
        if nxt != -1:
            rest = tail[nxt:]
    return (head.rstrip("\n") + "\n" + "\n".join(new_lines) + "\n"
            + ("\n" + rest.strip("\n") + "\n" if rest.strip("\n")
               else ""))


def refresh_serving_tables() -> list:
    """``bench.py --serving``: run the serving rows and fold them into
    the committed sweep tables (replacing any previous serving rows) —
    the device/host rows are left untouched."""
    here = os.path.dirname(os.path.abspath(__file__))
    rows = serving_rows() + fleet_rows() + frontdoor_rows()
    # stage medians double as BENCH_HISTORY points so otpu_perf --diff
    # guards the per-stage numbers run over run (bench-kind rows need a
    # positive lat_us; zero-width stages just don't emit a point)
    hist: dict = {}
    for r in rows:
        if not r.get("ok", True):
            continue
        for s, v in (r.get("stage_median_ms") or {}).items():
            if v > 0:
                hist[f"serving_stage/{r['coll']}/{s}"] = {
                    "key": f"serving_stage/{r['coll']}/{s}",
                    "lat_us": round(1000.0 * v, 1),
                    "k": int(r.get("req_decomposed", 0))}
        # front-door points: us-per-token for the spec A/B legs (so the
        # rolling-min gate catches a decode-throughput regression) and
        # the overload interactive exact p99
        if r.get("fd") and r.get("tokens_per_s", 0) > 0:
            key = f"serving_spec/us_per_token/{r['coll']}"
            hist[key] = {"key": key,
                         "lat_us": round(1e6 / r["tokens_per_s"], 1),
                         "k": int(r.get("nbytes", 0))}
        if (r.get("coll") == "serving_overload_interactive"
                and r.get("p99_exact_ms", 0) > 0):
            key = "serving_overload/interactive_p99"
            hist[key] = {"key": key,
                         "lat_us": round(1000.0 * r["p99_exact_ms"], 1),
                         "k": int(r.get("nbytes", 0))}
    if hist:
        append_history(sorted(hist.values(), key=lambda h: h["key"]),
                       "bench", "host_serving")
    try:
        with open(os.path.join(here, "BENCH_SWEEP.json")) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {"ndev": 0, "results": []}
    payload["results"] = [r for r in payload.get("results", [])
                          if not str(r.get("coll", "")).startswith(
                              "serving_")] + rows
    _atomic_write(os.path.join(here, "BENCH_SWEEP.json"),
                  json.dumps(payload, indent=1))
    # regenerate only the Serving section of the markdown table
    md_path = os.path.join(here, "BENCH_SWEEP.md")
    try:
        with open(md_path) as f:
            md = f.read()
    except OSError:
        md = "# Collective sweep\n"
    _atomic_write(md_path, _splice_md_section(
        md, "## Serving (Poisson open-loop",
        _serving_md_section(rows)))
    return rows


_RECOVERY = """
import json, sys
import ompi_tpu
from ompi_tpu.parallel.elastic import ElasticTrainer

w = ompi_tpu.init()
tr = ElasticTrainer(w, ckpt_dir=sys.argv[1], model_size=32,
                    global_batch=40, ckpt_every=4, respawn=False)
tr.train(20)
if tr.comm.rank == 0:
    print("RECOVERY " + json.dumps(tr.recoveries), flush=True)
ompi_tpu.finalize()
"""


def recovery_rows() -> list:
    """``bench.py --recovery``: detect→resume latency of the elastic
    train-through-failure loop.  One 5-rank job with a chaos kill
    schedule that fells three ranks at different steps — three full
    revoke→agree→shrink→restore recoveries — reporting p50/p99 of the
    end-to-end recovery time plus the median per-phase split.  The
    launcher-detection path (--enable-recovery), not the heartbeat
    ring, so the number is the runtime's recovery cost, not the
    detector timeout."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(_RECOVERY)
        script = f.name
    ckpt = tempfile.mkdtemp(prefix="otpu-recovery-")
    spec = "kill:rank=1,step=6;kill:rank=2,step=11;kill:rank=3,step=16"
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "5",
             "--enable-recovery", "--mca", "otpu_chaos_spec", spec,
             sys.executable, script, ckpt],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        line = next((ln for ln in proc.stdout.splitlines()
                     if "RECOVERY " in ln), None)
        if line is None:
            print(f"recovery bench failed (rc={proc.returncode}):\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            return [{"coll": "recovery_detect_to_resume", "ok": False}]
        recs = _json.loads(line.split("RECOVERY ", 1)[1])
        totals = sorted(r["total_ms"] for r in recs)
        phases = {}
        for ph in ("revoke", "agree", "shrink", "restore"):
            vals = sorted(r[ph + "_ms"] for r in recs if ph + "_ms" in r)
            if vals:
                phases[ph] = round(vals[len(vals) // 2], 3)
        return [{
            "coll": "recovery_detect_to_resume",
            "nbytes": len(totals),
            "p50_ms": round(totals[len(totals) // 2], 3),
            "p99_ms": round(totals[-1], 3),
            "min_ms": round(totals[0], 3),
            "phase_median_ms": phases,
        }]
    finally:
        import shutil

        os.unlink(script)
        shutil.rmtree(ckpt, ignore_errors=True)


def _recovery_md_section(rows) -> list:
    lines = ["", "## Recovery (elastic train-through-failure)",
             "",
             "Detect→resume latency of the full "
             "revoke→agree→shrink→restore recovery sequence "
             "(`bench.py --recovery`: 5-rank job, 3 chaos-scheduled "
             "rank kills).  Launcher detection; add the detector "
             "timeout for heartbeat-detected hangs.",
             "",
             "| rows | samples | p50 ms | p99 ms | min ms | "
             "phase medians (ms) |", "|---|---|---|---|---|---|"]
    for r in rows:
        if not r.get("ok", True):
            lines.append(f"| {r['coll']} | FAILED | - | - | - | - |")
            continue
        ph = "; ".join(f"{k}={v}" for k, v in
                       r.get("phase_median_ms", {}).items())
        lines.append(
            f"| {r['coll']} | {r['nbytes']} | {r['p50_ms']} | "
            f"{r['p99_ms']} | {r['min_ms']} | {ph} |")
    return lines


def refresh_recovery_tables() -> list:
    """``bench.py --recovery``: run the recovery rows and fold them
    into the committed sweep tables (replacing previous recovery rows);
    everything else is left untouched — the serving-table discipline."""
    here = os.path.dirname(os.path.abspath(__file__))
    rows = recovery_rows()
    try:
        with open(os.path.join(here, "BENCH_SWEEP.json")) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {"ndev": 0, "results": []}
    payload["results"] = [r for r in payload.get("results", [])
                          if not str(r.get("coll", "")).startswith(
                              "recovery_")] + rows
    _atomic_write(os.path.join(here, "BENCH_SWEEP.json"),
                  json.dumps(payload, indent=1))
    md_path = os.path.join(here, "BENCH_SWEEP.md")
    try:
        with open(md_path) as f:
            md = f.read()
    except OSError:
        md = "# Collective sweep\n"
    _atomic_write(md_path, _splice_md_section(
        md, "## Recovery (elastic train-through-failure)",
        _recovery_md_section(rows)))
    return rows


_QUANT_WIRE = """
import json, time
import numpy as np
import ompi_tpu
from ompi_tpu.mca.coll import quant
from ompi_tpu.runtime import spc

w = ompi_tpu.init()
n = (4 << 20) // 4
base = np.stack([np.random.default_rng([7, r]).standard_normal(n)
                 for r in range(w.size)]).astype(np.float32)
mine = base[w.rank]
exact = base.astype(np.float64).sum(0)
w.barrier()
got = np.asarray(w.allreduce(mine))          # warm
reps = 3
t0 = time.perf_counter()
for _ in range(reps):
    got = np.asarray(w.allreduce(mine))
dt = (time.perf_counter() - t0) / reps
rel = float(np.max(np.abs(got - exact)) / max(1e-12,
                                              np.max(np.abs(exact))))
st = quant.wire_stats()
if w.rank == 0:
    print("QUANTWIRE " + json.dumps({
        "lat_us": round(dt * 1e6, 1),
        "eff_gbs": round(n * 4 / dt / 1e9, 4),
        "wire_orig": st["orig"], "wire_enc": st["enc"],
        "wire_saved": spc.read("quant_wire_bytes_saved"),
        "max_rel_err": rel}), flush=True)
ompi_tpu.finalize()
"""


def _quant_wire_rows() -> list:
    """Wire-path evidence: the 4MB host allreduce over loopback tcp
    (the PR 4 fastpath wire) with quantize-on-pack ON vs OFF — latency,
    effective GB/s, measured bytes-on-wire (orig vs encoded out of the
    codec stage's own accounting), and max relative error vs the f64
    exact sum.  rd forced so both runs move the same message pattern."""
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(_QUANT_WIRE)
        script = f.name
    rows = []
    try:
        for name, wire in (("quant_wire_off_4MB", "0"),
                           ("quant_wire_int8_4MB", "1")):
            proc = subprocess.run(
                [sys.executable, "-m", "ompi_tpu.tools.tpurun",
                 "-n", "2", "--fake-nodes", "2",
                 "--mca", "otpu_coll_sm_coll_priority", "0",
                 "--mca", "otpu_coll_quant_wire", wire,
                 "--mca", "otpu_coll_tuned_allreduce_algorithm",
                 "recursive_doubling",
                 "--mca", "pml_ob1_stripe", "0",
                 "--mca", "pml_ob1_rget_limit", "0",
                 sys.executable, script],
                capture_output=True, text=True, timeout=300,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            line = next((ln for ln in proc.stdout.splitlines()
                         if "QUANTWIRE " in ln), None)
            if proc.returncode or line is None:
                print(f"quant wire bench ({name}) failed "
                      f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}",
                      file=sys.stderr)
                rows.append({"coll": name, "ok": False})
                continue
            rep = json.loads(line.split("QUANTWIRE ", 1)[1])
            row = {"coll": name, "nbytes": 4 << 20}
            row.update(rep)
            if rep.get("wire_enc"):
                row["wire_ratio"] = round(rep["wire_orig"]
                                          / rep["wire_enc"], 2)
            rows.append(row)
    finally:
        os.unlink(script)
    return rows


def _quant_kv_row(codec: str = "int8") -> dict:
    """KV-slab evidence: encode+decode cost per 4096-elem block, the
    capacity multiplier (raw slot bytes / encoded slot bytes — the
    users-per-chip factor), and the codec's measured error."""
    import numpy as np

    from ompi_tpu.mca.coll import quant

    elems, reps = 4096, 64
    rng = np.random.default_rng(11)
    blocks = rng.standard_normal((reps, elems)).astype(np.float32)
    enc0 = quant.encode_f32(blocks[0], codec)
    t0 = time.perf_counter()
    worst = 0.0
    for i in range(reps):
        enc = quant.encode_f32(blocks[i], codec)
        dec = quant.decode_f32(enc, codec, elems)
        worst = max(worst, float(np.max(np.abs(dec - blocks[i]))
                                 / np.max(np.abs(blocks[i]))))
    dt = (time.perf_counter() - t0) / reps
    return {"coll": f"quant_kv_{codec}", "nbytes": elems * 4,
            "lat_us": round(dt * 1e6, 1),
            "enc_bytes": int(enc0.nbytes),
            "capacity_x": round(elems * 4 / enc0.nbytes, 2),
            "max_rel_err": worst}


def quant_rows() -> list:
    """``bench.py --quant``: the host rows of the codec — wire and KV.
    The device tier's kernels (``ops/pallas_quant.py``) get their rows
    from a run on the chip (ROADMAP A2), not from this host mode."""
    return _quant_wire_rows() + [_quant_kv_row("int8"),
                                 _quant_kv_row("bf16")]


def _quant_md_section(rows) -> list:
    lines = ["", "## Quant (block-scale quantized collectives & KV)",
             "",
             "`bench.py --quant`: the coll/quant codec across its "
             "three datapaths.  Wire rows are the 4MB loopback-tcp "
             "host allreduce with quantize-on-pack off/on (`wire B` "
             "is measured bytes-on-wire out of the codec stage; the "
             "byte win pays on a real DCN wire — loopback moves at "
             "memcpy speed, so latency is codec-dominated there).  "
             "KV rows are per-block encode+decode cost and the slots-"
             "per-worker capacity multiplier.",
             "",
             "| row | bytes | lat us | eff GB/s | wire B (orig→enc) | "
             "ratio/cap x | max rel err |",
             "|---|---|---|---|---|---|---|"]
    for r in rows:
        if not r.get("ok", True):
            lines.append(f"| {r['coll']} | FAILED | - | - | - | - | "
                         "- |")
            continue
        wire = (f"{r['wire_orig']}→{r['wire_enc']}"
                if r.get("wire_orig") else "-")
        factor = r.get("wire_ratio", r.get("capacity_x", "-"))
        lines.append(
            f"| {r['coll']} | {r.get('nbytes', '-')} | "
            f"{r.get('lat_us', '-')} | {r.get('eff_gbs', '-')} | "
            f"{wire} | {factor} | "
            f"{round(r['max_rel_err'], 6) if 'max_rel_err' in r else '-'} |")
    return lines


def refresh_quant_tables() -> list:
    """``bench.py --quant``: run the quant rows, fold them into the
    committed sweep tables (replacing previous quant rows — the
    serving-table discipline), and append the wire-on row as a
    BENCH_HISTORY point so ``otpu_perf --diff`` guards it."""
    here = os.path.dirname(os.path.abspath(__file__))
    rows = quant_rows()
    try:
        with open(os.path.join(here, "BENCH_SWEEP.json")) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {"ndev": 0, "results": []}
    payload["results"] = [r for r in payload.get("results", [])
                          if not str(r.get("coll", "")).startswith(
                              "quant_")] + rows
    _atomic_write(os.path.join(here, "BENCH_SWEEP.json"),
                  json.dumps(payload, indent=1))
    md_path = os.path.join(here, "BENCH_SWEEP.md")
    try:
        with open(md_path) as f:
            md = f.read()
    except OSError:
        md = "# Collective sweep\n"
    _atomic_write(md_path, _splice_md_section(
        md, "## Quant (block-scale quantized collectives & KV)",
        _quant_md_section(rows)))
    hist = [{"key": r["coll"], "lat_us": r["lat_us"], "k": 3}
            for r in rows
            if r.get("ok", True) and r.get("lat_us")
            and str(r["coll"]).startswith("quant_wire_")]
    if hist:
        append_history(hist, "bench", "host_tcp_n2")
    return rows


_MOE_WORKER = """
import json, os, shutil, tempfile, time
import ompi_tpu
from ompi_tpu.parallel.elastic import ElasticTrainer
from ompi_tpu.parallel.moe import MoeTrainer

E, D, T, STEPS, WARM = 8, 32, 256, 24, 4
w = ompi_tpu.init()
# every rank must see the SAME checkpoint tree: derive it from the
# coord address (identical across ranks, unique per live job)
base = os.path.join(tempfile.gettempdir(), "otpu_moebench_"
                    + os.environ["OTPU_COORD"].replace(":", "_")
                    .replace("/", "_"))
if w.rank == 0:
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
w.barrier()
tr = MoeTrainer(w, base + "/moe", n_experts=E, expert_dim=D,
                tokens_per_step=T, ckpt_every=1 << 30)
tr.train(WARM)
w.barrier(); t0 = time.perf_counter()
tr.train(WARM + STEPS)
w.barrier(); moe_s = time.perf_counter() - t0
rep = tr.report()
dn = ElasticTrainer(w, base + "/dense", model_size=E * D,
                    global_batch=T, ckpt_every=1 << 30)
dn.train(WARM)
w.barrier(); t0 = time.perf_counter()
dn.train(WARM + STEPS)
w.barrier(); dense_s = time.perf_counter() - t0
if w.rank == 0:
    rows = [
        {"coll": "moe_host_n2", "nbytes": T, "ok": True,
         "lat_us": round(moe_s / STEPS * 1e6, 1),
         "tokens_per_s": round(T * STEPS / moe_s, 1),
         "imbalance": rep["imbalance_max"],
         "dropped": rep["dropped"]},
        {"coll": "moe_dense_n2", "nbytes": T, "ok": True,
         "lat_us": round(dense_s / STEPS * 1e6, 1),
         "tokens_per_s": round(T * STEPS / dense_s, 1)},
    ]
    print("MOEBENCH " + json.dumps(rows))
ompi_tpu.finalize()
"""


def moe_rows(n: int = 2) -> list:
    """``bench.py --moe``: expert-parallel training throughput vs the
    dense trainer at MATCHED params (same weight count E*D, same token
    batch, same lr schedule) over one tpurun world — tokens/sec, the
    per-step latency, and the gating load-imbalance factor (a pure
    function of the seeded plan, so the committed value is exact, not
    a noisy measurement)."""
    return _run_history_worker(_MOE_WORKER, "MOEBENCH", n)


def _moe_md_section(rows) -> list:
    lines = ["", "## MoE (expert-parallel host trainer vs dense)",
             "",
             "`bench.py --moe`: the `parallel/moe` expert-parallel "
             "trainer (top-2 gating, capacity-factor dispatch over "
             "the ragged alltoallv/allgatherv tier) against the dense "
             "`parallel/elastic` trainer at matched parameter count "
             "and token batch.  `imbalance` is max-expert-load over "
             "mean — deterministic for the committed seed, so it is "
             "pinned exactly; latency/token rows carry the usual "
             "CI-host noise bands.",
             "",
             "| row | tokens | step us | tokens/s | imbalance | "
             "dropped |",
             "|---|---|---|---|---|---|"]
    for r in rows:
        if not r.get("ok", True):
            lines.append(f"| {r['coll']} | FAILED | - | - | - | - |")
            continue
        lines.append(
            f"| {r['coll']} | {r.get('nbytes', '-')} | "
            f"{r.get('lat_us', '-')} | {r.get('tokens_per_s', '-')} | "
            f"{r.get('imbalance', '-')} | {r.get('dropped', '-')} |")
    return lines


def refresh_moe_tables() -> list:
    """``bench.py --moe``: run the MoE-vs-dense rows, fold them into
    the committed sweep tables (replacing previous moe rows — the
    serving-table discipline), and append them as BENCH_HISTORY points
    so ``otpu_perf --diff`` guards the per-step latency."""
    here = os.path.dirname(os.path.abspath(__file__))
    rows = moe_rows()
    try:
        with open(os.path.join(here, "BENCH_SWEEP.json")) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {"ndev": 0, "results": []}
    payload["results"] = [r for r in payload.get("results", [])
                          if not str(r.get("coll", "")).startswith(
                              "moe_")] + rows
    _atomic_write(os.path.join(here, "BENCH_SWEEP.json"),
                  json.dumps(payload, indent=1))
    md_path = os.path.join(here, "BENCH_SWEEP.md")
    try:
        with open(md_path) as f:
            md = f.read()
    except OSError:
        md = "# Collective sweep\n"
    _atomic_write(md_path, _splice_md_section(
        md, "## MoE (expert-parallel host trainer vs dense)",
        _moe_md_section(rows)))
    hist = [{"key": r["coll"], "lat_us": r["lat_us"], "k": 3}
            for r in rows if r.get("ok", True) and r.get("lat_us")]
    if hist:
        append_history(hist, "bench", "host_sm_n2")
    return rows


_STAGING_OSU = """
import json, statistics, sys, time
import numpy as np
import ompi_tpu
from ompi_tpu.mca.accelerator.jax_acc import staging

w = ompi_tpu.init()
x = np.ones((4 << 20) // 4, np.float32)
for _ in range(3):
    w.allreduce(x)
# min-of-many: the pool's per-call win (one warm 1MB checkout per ring
# call) is percent-scale, far below this 1-core harness's per-call
# scheduling jitter — the latency FLOOR is the comparable statistic
lat = []
for _ in range(24):
    w.barrier()
    t0 = time.perf_counter()
    w.allreduce(x)
    lat.append(time.perf_counter() - t0)
if w.rank == 0:
    print("STAGING " + json.dumps(
        [min(lat), staging.hits, staging.misses]))
ompi_tpu.finalize()
"""


def staging_micro_row() -> dict:
    """Mechanism-level rcache/grdma-reuse row: warmed pool checkout vs
    fresh alloc + page-touch for the ring's per-step 1MB buffer.  This
    is the robust measurement — the end-to-end 4MB rows below sit
    within this 1-core harness's run-to-run noise (the ~30µs/step tax
    is <1% of a 25ms host collective; it matters when the transport is
    fast, i.e. on real hardware)."""
    import numpy as np

    from ompi_tpu.mca.accelerator.jax_acc import _StagingPool

    n, reps = 1 << 20, 50
    t0 = time.perf_counter()
    for _ in range(reps):
        b = np.empty(n, np.float32)
        b[::4096] = 1.0              # touch the fresh pages
    t_fresh = (time.perf_counter() - t0) / reps
    pool = _StagingPool(max_bytes=1 << 30, enabled=True)
    pool.release(pool.acquire(n, np.float32))
    t0 = time.perf_counter()
    for _ in range(reps):
        b = pool.acquire(n, np.float32)
        b[::4096] = 1.0
        pool.release(b)
    t_pool = (time.perf_counter() - t0) / reps
    return {"coll": "staging_reuse_micro_1MB", "nbytes": 1 << 20,
            "fresh_us": round(t_fresh * 1e6, 1),
            "pooled_us": round(t_pool * 1e6, 1),
            "ratio": round(t_fresh / max(t_pool, 1e-9), 2)}


def threads_pool_row() -> dict:
    """Mechanism row for the mca/threads substrate: 4MB strided-vector
    pack through a 2-worker native pool vs the single-thread native
    loop.  On a 1-core harness the pool COSTS ~1.6x (cross-thread
    chunking with no second core) — which is exactly why
    ``default_workers`` returns 1 there and the convertor keeps its
    serial path; a many-core TPU-host run shows the fan-out paying
    off.  ``effective_workers`` records what this host actually uses."""
    import numpy as np

    from ompi_tpu.datatype import core as dt_core
    from ompi_tpu.datatype import convertor as conv_mod
    from ompi_tpu.datatype.convertor import Convertor
    from ompi_tpu.base.var import registry
    from ompi_tpu.mca.threads import base as threads_base

    vec = dt_core.vector(2, 1, 2, dt_core.FLOAT32)
    n = (4 << 20) // vec.size
    buf = np.random.default_rng(0).standard_normal(
        n * (vec.extent // 4)).astype(np.float32)
    reps = 10

    def run_pack():
        t0 = time.perf_counter()
        for _ in range(reps):
            Convertor(vec, n, buf).pack()
        return (time.perf_counter() - t0) / reps

    var = registry.lookup("otpu_threads_pool_workers")
    old_var = var.value
    threads_base.shutdown_pool()
    var.set(2)                           # force the pool path for the
    try:                                 # mechanism measurement
        pool = threads_base.get_pool()   # spawn workers OUTSIDE the
        run_pack()                       # timing + one warm-up rep
        pool_ran = bool(getattr(pool, "parallel_pack", False))
        t_pool = run_pack()
    finally:
        var.set(old_var)
        threads_base.shutdown_pool()
    old = conv_mod._POOL_PACK_MIN
    conv_mod._POOL_PACK_MIN = 1 << 62    # force the single-thread loop
    try:
        t_serial = run_pack()
    finally:
        conv_mod._POOL_PACK_MIN = old
    return {"coll": "threads_pool_pack_4MB", "nbytes": 4 << 20,
            "serial_us": round(t_serial * 1e6, 1),
            "pooled_us": round(t_pool * 1e6, 1),
            "effective_workers": threads_base.default_workers(),
            "pool_path_ran": pool_ran,
            "ratio": round(t_serial / max(t_pool, 1e-9), 2),
            "note": ("2-worker pool forced for the measurement; <1.0 "
                     "on a 1-core harness is EXPECTED and is why "
                     "default_workers()==1 keeps the serial path there"
                     if pool_ran else
                     "native substrate unavailable: both columns are "
                     "the serial path (python fallback has no parallel "
                     "pack)")}


def host_staging_points() -> list:
    """rcache/grdma-reuse rows (rcache_grdma.c): the mechanism
    microbenchmark (robust) plus the end-to-end 4MB allreduce pair
    (recorded for completeness; within noise on the 1-core harness)."""
    import json as _json
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_STAGING_OSU)
        script = f.name
    rows = []
    try:
        rows.append(staging_micro_row())
        # ALTERNATE pool/nopool jobs and keep each mode's best run: the
        # two configurations used to run minutes apart, so 1-core host
        # drift (±10%) dwarfed the pool's per-call win and the e2e
        # ratio was pure noise.  Paired best-of-N isolates the
        # mechanism the same way the perf-guard's interleaved reps do.
        lat: dict = {}
        stats: dict = {}
        for _rep in range(3):
            for mode, flag in (("pool", "1"), ("nopool", "0")):
                proc = subprocess.run(
                    [sys.executable, "-m", "ompi_tpu.tools.tpurun",
                     "-n", "4",
                     "--mca", "accelerator_jax_staging_pool", flag,
                     sys.executable, script],
                    capture_output=True, text=True, timeout=240,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"))
                line = next((ln for ln in proc.stdout.splitlines()
                             if "STAGING" in ln), None)
                if proc.returncode or line is None:
                    print(f"staging bench ({mode}) failed "
                          f"(rc={proc.returncode}):"
                          f"\n{proc.stderr[-1500:]}", file=sys.stderr)
                    continue
                t, hits, misses = _json.loads(
                    line.split("STAGING ", 1)[1])
                if mode not in lat or t < lat[mode]:
                    lat[mode] = t
                    stats[mode] = (hits, misses)
        for mode in ("pool", "nopool"):
            if mode in lat:
                rows.append({"coll": f"allreduce_4MB_staging_{mode}",
                             "nbytes": 4 << 20,
                             "fw_lat_us": round(lat[mode] * 1e6, 1),
                             "pool_hits": stats[mode][0],
                             "pool_misses": stats[mode][1]})
        if "pool" in lat and "nopool" in lat:
            rows.append({"coll": "staging_pool_e2e",
                         "nbytes": 4 << 20,
                         "ratio": round(lat["nopool"] / lat["pool"], 3),
                         "note": "paired best-of-3 (alternating jobs); "
                                 "the mechanism micro row is the "
                                 "per-checkout claim"})
    finally:
        os.unlink(script)
    return rows


_FASTPATH_TCP = """
import json, statistics, sys, time
import numpy as np
import ompi_tpu
from ompi_tpu.runtime import spc

w = ompi_tpu.init()
nbytes = 4 << 20
WINDOW = 4
x = np.ones(nbytes, np.uint8)
bufs = [np.empty_like(x) for _ in range(WINDOW)]
ack = np.zeros(1, np.float64)
def once():
    if w.rank == 0:
        reqs = [w.isend(x, dest=1, tag=9) for _ in range(WINDOW)]
        for r in reqs:
            r.wait()
        w.recv(ack, source=1, tag=10)
    else:
        reqs = [w.irecv(bufs[i], source=0, tag=9) for i in range(WINDOW)]
        for r in reqs:
            r.wait()
        w.send(ack, dest=0, tag=10)
for _ in range(2):
    once()
# the 1-core harness is bimodal (scheduler-paced slow windows vs
# memcpy-bound fast windows, in BOTH wire implementations): the best
# window measures the wire MECHANISM, the median measures the host
ts = []
for _ in range(12):
    w.barrier()
    t0 = time.perf_counter()
    once()
    ts.append(time.perf_counter() - t0)
if w.rank == 0:
    c = spc.counters()
    print("FASTPATH_TCP " + json.dumps(
        [WINDOW * nbytes / min(ts) / 1e9,
         WINDOW * nbytes / statistics.median(ts) / 1e9,
         c.get("fastpath_hdr_fast", 0),
         c.get("fastpath_hdr_pickle", 0),
         c.get("fastpath_payload_copies", 0),
         c.get("fastpath_sendmsg", 0)]))
ompi_tpu.finalize()
"""


_FASTPATH_4K = """
import json, statistics, sys, time
import numpy as np
import ompi_tpu
from ompi_tpu.runtime import spc

w = ompi_tpu.init()
x = np.ones(1024, np.float32)          # 4KB
for _ in range(5):
    w.allreduce(x)
lat = []
for _ in range(30):
    w.barrier()
    t0 = time.perf_counter()
    w.allreduce(x)
    lat.append(time.perf_counter() - t0)
if w.rank == 0:
    c = spc.counters()
    print("FASTPATH_4K " + json.dumps(
        [statistics.median(lat),
         c.get("fastpath_eager_lane", 0),
         c.get("fastpath_sched_hits", 0)]))
ompi_tpu.finalize()
"""


# ---------------------------------------------------------------------
# otpu-prof perf-regression history plane (BENCH_HISTORY.jsonl)
# ---------------------------------------------------------------------

_HISTORY_WORKER = """
import json, os, time
import numpy as np
import ompi_tpu
from ompi_tpu.api import op

w = ompi_tpu.init()
K = int(os.environ.get("OTPU_BENCH_HISTORY_REPS", "6"))
BATCH = int(os.environ.get("OTPU_BENCH_HISTORY_BATCH", "30"))
points = os.environ.get(
    "OTPU_BENCH_HISTORY_POINTS",
    "allreduce:4096,allreduce:65536,pingpong:4096")
out = []
for spec in points.split(","):
    kind, nbytes = spec.strip().split(":")
    nbytes = int(nbytes)
    if kind == "allreduce":
        x = np.ones(max(1, nbytes // 4), np.float32)
        def once():
            for _ in range(BATCH):
                w.allreduce(x, op.SUM)
    else:                               # pingpong (2-rank halves)
        x = np.ones(nbytes, np.uint8)
        buf = np.empty_like(x)
        peer = (w.rank + 1) % 2
        def once():
            for _ in range(BATCH):
                if w.rank == 0:
                    w.send(x, dest=1, tag=7)
                    w.recv(buf, source=1, tag=8)
                elif w.rank == 1:
                    w.recv(buf, source=0, tag=7)
                    w.send(x, dest=0, tag=8)
    once()                              # warmup
    best = float("inf")
    for _ in range(K):                  # min-of-k: fast-mode statistic
        w.barrier()
        t0 = time.perf_counter()
        once()
        best = min(best, (time.perf_counter() - t0) / BATCH)
    out.append({"key": f"{kind}_{nbytes}b_n{w.size}",
                "lat_us": round(best * 1e6, 1), "k": K,
                "batch": BATCH, "nbytes": nbytes})
if w.rank == 0:
    print("HISTORY " + json.dumps(out))
ompi_tpu.finalize()
"""

_LADDER_WORKER = """
import json, os, time
import numpy as np
import ompi_tpu
from ompi_tpu.api import op
from ompi_tpu.base.var import registry
from ompi_tpu.mca.coll.tuned import _MENUS

w = ompi_tpu.init()
K = int(os.environ.get("OTPU_BENCH_LADDER_REPS", "3"))
colls = os.environ.get("OTPU_BENCH_LADDER_COLLS",
                       "allreduce,bcast").split(",")
sizes = [int(s) for s in os.environ.get(
    "OTPU_BENCH_LADDER_SIZES", "4096,65536,1048576").split(",")]
out = []
for coll in colls:
    force = registry.lookup(f"otpu_coll_tuned_{coll}_algorithm")
    for nbytes in sizes:
        x = np.ones(max(1, nbytes // 4), np.float32)
        for alg in sorted(_MENUS[coll]):
            force.set(alg)              # every rank runs the same loop
            batch = max(3, min(20, (256 << 10) // max(1, nbytes)))
            def once():
                for _ in range(batch):
                    if coll == "allreduce":
                        w.allreduce(x, op.SUM)
                    else:
                        w.bcast(x, root=0)
            try:
                once()
                best = float("inf")
                for _ in range(K):
                    w.barrier()
                    t0 = time.perf_counter()
                    once()
                    best = min(best, (time.perf_counter() - t0) / batch)
                out.append({"coll": coll, "nbytes": nbytes,
                            "algorithm": alg,
                            "lat_us": round(best * 1e6, 1), "k": K})
            except Exception as exc:
                out.append({"coll": coll, "nbytes": nbytes,
                            "algorithm": alg, "error": str(exc)[:120],
                            "lat_us": -1.0, "k": K})
        force.set("")
if w.rank == 0:
    print("LADDER " + json.dumps(out))
ompi_tpu.finalize()
"""


def history_file() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.environ.get("OTPU_BENCH_HISTORY_FILE",
                          os.path.join(here, "BENCH_HISTORY.jsonl"))


def _run_history_worker(body: str, marker: str, n: int,
                        extra_mca=(), extra_argv=(),
                        extra_env=()) -> list:
    """One tpurun job over the PML wire path (coll/sm pushed below
    coll/tuned so the rows measure the datapath the stage clocks cover
    — and so a chaos wire fault actually lands in the numbers)."""
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(body)
        script = f.name
    try:
        argv = [sys.executable, "-m", "ompi_tpu.tools.tpurun",
                "-n", str(n),
                "--mca", "otpu_coll_sm_coll_priority", "0"]
        argv += list(extra_argv)
        for k, v in extra_mca:
            argv += ["--mca", k, v]
        argv += [sys.executable, script]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.update(dict(extra_env))
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=600,
            env=env)
        line = next((ln for ln in proc.stdout.splitlines()
                     if marker in ln), None)
        if proc.returncode or line is None:
            print(f"history bench failed (rc={proc.returncode}):\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            return []
        return json.loads(line.split(marker + " ", 1)[1])
    finally:
        os.unlink(script)


def append_history(rows: list, kind: str, topology: str) -> list:
    """Stamp measurement rows into v1 history rows (one run id per
    call) and append them to the history file."""
    run = f"r{int(time.time() * 1000)}"
    t = time.time()
    stamped = []
    for r in rows:
        row = {"v": 1, "kind": kind, "run": run, "t": t,
               "topology": topology, "host": os.uname().nodename}
        row.update(r)
        stamped.append(row)
    path = history_file()
    with open(path, "a") as f:
        for row in stamped:
            f.write(json.dumps(row) + "\n")
    return stamped


def history_rows(n: int = 2) -> list:
    """``--history``: min-of-k host-datapath latency points appended as
    one run to BENCH_HISTORY.jsonl (the otpu_perf --diff input)."""
    rows = _run_history_worker(_HISTORY_WORKER, "HISTORY", n)
    return append_history(rows, "bench", f"host_sm_n{n}")


def reactor_history_rows(n: int = 3, native: bool = True) -> list:
    """``--reactor-history``: the native-reactor acceptance lane — the
    same min-of-k worker forced onto btl/tcp (``--fake-nodes`` so sm
    declines every peer and the eager 4KB allreduce rides the wire the
    epoll reactor drains).  Run once with ``native=False`` (pure-Python
    selector loop, the "before" baseline) and once with the default
    (reactor on, the "after"): both land under the same
    ``host_tcp_n{n}`` topology and identical keys, so ``otpu_perf
    --diff`` compares reactor-on against the reactor-off min — the hard
    4KB-eager latency budget.  Pingpong needs exactly 2 ranks, so the
    default point set here is allreduce-only (override via
    OTPU_BENCH_HISTORY_POINTS)."""
    extra_env = []
    if "OTPU_BENCH_HISTORY_POINTS" not in os.environ:
        extra_env.append(("OTPU_BENCH_HISTORY_POINTS",
                          "allreduce:4096,allreduce:65536"))
    extra_mca = () if native else (("otpu_progress_native", "0"),)
    rows = _run_history_worker(
        _HISTORY_WORKER, "HISTORY", n,
        extra_mca=extra_mca, extra_argv=("--fake-nodes", str(n)),
        extra_env=extra_env)
    return append_history(rows, "bench", f"host_tcp_n{n}")


def ladder_host_rows(n: int = 2) -> list:
    """``--ladder``: the measured per-(topology, coll, size, algorithm)
    sweep the self-tuning rules file (ROADMAP item 3) is derived from.
    Failed (coll, size, alg) cells carry ``error`` and lat_us -1 and
    are excluded from history (otpu_perf rejects non-positive rows)."""
    rows = _run_history_worker(_LADDER_WORKER, "LADDER", n)
    good = [r for r in rows if r.get("lat_us", -1) > 0]
    bad = [r for r in rows if r.get("lat_us", -1) <= 0]
    for r in bad:
        print(f"ladder: {r['coll']}/{r['nbytes']}/{r['algorithm']} "
              f"failed: {r.get('error')}", file=sys.stderr)
    return append_history(good, "ladder", f"host_sm_n{n}") + bad


def fastpath_points() -> list:
    """fastpath rows (BENCH_SWEEP schema): the zero-copy host-datapath
    evidence.  (a) ``fastpath_tcp_loopback``: 2-rank streaming bandwidth
    over btl/tcp's sendmsg-coalesced wire (fake-nodes so tcp carries the
    FRAG stream; acceptance: >=1.5x the pre-fastpath ``pt2pt_tcp_frag``
    figure on the same host), with the SPC copy/header counters in the
    row.  (b) ``fastpath_allreduce_4KB``: the small-message host
    allreduce latency the eager lane + schedule cache attack.  The
    staging e2e evidence is the existing ``staging_pool_e2e`` row."""
    import json as _json
    import subprocess
    import tempfile

    rows = []
    for name, body, cmd_extra in (
            ("fastpath_tcp_loopback", _FASTPATH_TCP,
             ["--fake-nodes", "2", "--mca", "pml_ob1_stripe", "0",
              "--mca", "pml_ob1_rget_limit", "0"]),
            # ^sm_coll isolates coll/tuned (on one host coll/sm owns
            # sub-slot payloads): this row measures the eager lane +
            # schedule cache the fastpath PR added to the tuned ladder
            ("fastpath_allreduce_4KB", _FASTPATH_4K,
             ["--mca", "coll", "^sm_coll"])):
        with tempfile.NamedTemporaryFile("w", suffix=".py",
                                         delete=False) as f:
            f.write(body)
            script = f.name
        try:
            n = "2" if name == "fastpath_tcp_loopback" else "4"
            proc = subprocess.run(
                [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", n,
                 *cmd_extra, sys.executable, script],
                capture_output=True, text=True, timeout=300,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            tagname = ("FASTPATH_TCP" if name == "fastpath_tcp_loopback"
                       else "FASTPATH_4K")
            line = next((ln for ln in proc.stdout.splitlines()
                         if tagname in ln), None)
            if proc.returncode or line is None:
                print(f"fastpath bench ({name}) failed "
                      f"(rc={proc.returncode}):\n{proc.stderr[-1500:]}",
                      file=sys.stderr)
                continue
            vals = _json.loads(line.split(tagname + " ", 1)[1])
            if name == "fastpath_tcp_loopback":
                bw_best, bw_med, hfast, hpickle, copies, sendmsg = vals
                rows.append({"coll": name, "nbytes": 4 << 20,
                             "fw_bw_gbs": round(bw_best, 4),
                             "fw_bw_med_gbs": round(bw_med, 4),
                             "hdr_fast": int(hfast),
                             "hdr_pickle": int(hpickle),
                             "payload_copies": int(copies),
                             "sendmsg_calls": int(sendmsg),
                             "note": "fw_bw_gbs = best window (wire "
                                     "mechanism); median tracks the "
                                     "bimodal 1-core scheduler"})
            else:
                lat, lane, hits = vals
                rows.append({"coll": name, "nbytes": 4096,
                             "fw_lat_us": round(lat * 1e6, 1),
                             "eager_lane_calls": int(lane),
                             "sched_cache_hits": int(hits)})
        finally:
            os.unlink(script)
    return rows


MULTIDEV_SIZES = (8, 4096, 262144, 4 << 20)
MULTIDEV_SPOT = 262144
#: acceptable fw-vs-raw ratio band for the 8-virtual-device table once
#: raw baselines are pinned to identical program shapes
MULTIDEV_BAND = (0.8, 1.25)


def multidev_child() -> None:
    """Child body: 8-virtual-CPU-device ratio sweep (correctness-grade).

    Ratios here measure framework dispatch + algorithm choice against
    raw shard_map programs on the SAME 8-device CPU mesh — they make
    tuned-ladder and xla-program regressions visible without pod access
    (SURVEY.md §4's "fake backend MPI never had").  They are NOT
    bandwidth numbers: CPU rings move bytes through host memory.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    b = DeviceBench()
    rows = []
    for nbytes in MULTIDEV_SIZES:
        rows.append(b.point("allreduce", nbytes))
    for coll in ("bcast", "allgather", "reduce_scatter"):
        rows.append(b.point(coll, MULTIDEV_SPOT))
    try:
        rows.append(b.persistent_point(MULTIDEV_SPOT, iters=10))
    except Exception as exc:
        # one failing row must not cost the whole 8-device table
        print(f"multidev persistent failed: {exc}", file=sys.stderr)
    # regression-guard contract: with raw baselines pinned to identical
    # program shapes, every ratio must sit in a band around 1.0 —
    # below = dispatch/selection regression, above = the baselines
    # diverged again and the table stopped guarding anything.
    # (Tiny payloads are latency-noise-bound: band-checked only at
    # >=4KB.)  tests/test_bench_table.py fails CI on out-of-band rows.
    for r in rows:
        if r.get("nbytes", 0) >= 4096:
            r["in_band"] = bool(
                MULTIDEV_BAND[0] <= r["ratio"] <= MULTIDEV_BAND[1])
    bad = [r for r in rows if r.get("in_band") is False]
    if bad:
        print("multidev rows OUT OF BAND: "
              + ", ".join(f"{r['coll']}/{r['nbytes']}={r['ratio']}"
                          for r in bad), file=sys.stderr)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_SWEEP_8DEV.json"), "w") as f:
        json.dump({"ndev": b.ndev, "grade": "correctness",
                   "band": list(MULTIDEV_BAND), "results": rows},
                  f, indent=1)
    import ompi_tpu

    ompi_tpu.finalize()


def multidev_sweep(ndev: int = 8) -> list:
    """Run the virtual-multidevice sweep hermetically (fresh interpreter:
    the parent's jax may be pinned to one real TPU chip) and return its
    rows (empty on failure)."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--multidev-child"],
        env=env, cwd=here, capture_output=True, text=True, timeout=900)
    if proc.returncode:
        print(f"multidev sweep failed (rc={proc.returncode}):\n"
              f"{proc.stderr[-1500:]}", file=sys.stderr)
        return []
    try:
        with open(os.path.join(here, "BENCH_SWEEP_8DEV.json")) as f:
            return json.load(f)["results"]
    except (OSError, KeyError, ValueError):
        return []


def emit_metric(value: float, ratio: float, devs) -> None:
    """The ONE driver-contract JSON line (single emission point), with
    the devices it was measured on."""
    print(json.dumps({
        "metric": "osu_allreduce_bus_bw_16MB_f32",
        "value": value, "unit": "GB/s", "vs_baseline": ratio,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}}))


def host_rows() -> list:
    """Configs #1-#2 (host path, JAX_PLATFORMS=cpu subprocesses): these
    need no accelerator at all."""
    rows = []
    try:
        rows.append(host_ring_smoke())
    except Exception as exc:
        print(f"ring smoke failed: {exc}", file=sys.stderr)
    try:
        rows.extend(host_allreduce_points())
    except Exception as exc:
        print(f"host allreduce failed: {exc}", file=sys.stderr)
    try:
        rows.extend(host_rget_points())
    except Exception as exc:
        print(f"rget bench failed: {exc}", file=sys.stderr)
    try:
        rows.extend(host_part_points())
    except Exception as exc:
        print(f"partitioned pingpong bench failed: {exc}", file=sys.stderr)
    try:
        rows.extend(host_staging_points())
    except Exception as exc:
        print(f"staging bench failed: {exc}", file=sys.stderr)
    try:
        rows.append(threads_pool_row())
    except Exception as exc:
        print(f"threads pool bench failed: {exc}", file=sys.stderr)
    try:
        rows.extend(fastpath_points())
    except Exception as exc:
        print(f"fastpath bench failed: {exc}", file=sys.stderr)
    return rows


def _table(rows) -> list:
    out = ["| coll | bytes | fw lat us | raw lat us | fw GB/s | "
           "raw GB/s | ratio |",
           "|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['coll']} | {r.get('nbytes', '-')} | "
            f"{r.get('fw_lat_us', '-')} | "
            f"{r.get('raw_lat_us', '-')} | "
            f"{r.get('fw_bw_gbs', '-')} | "
            f"{r.get('raw_bw_gbs', '-')} | "
            f"{r.get('ratio', '-')} |")
    return out


def _atomic_write(path: str, text: str) -> None:
    """Write-then-replace: a mid-write failure must never leave a
    truncated file (tier-1 pins read the committed tables)."""
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def write_sweep(ndev, results, multidev_rows, mfu=None) -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    # serving/recovery/quant rows are refreshed by `bench.py --serving`
    # / `--recovery` / `--quant`, not by the sweep: carry the committed
    # ones forward so a sweep refresh cannot erase them
    for prefix in ("serving_", "recovery_", "quant_"):
        if not any(str(r.get("coll", "")).startswith(prefix)
                   for r in results):
            try:
                with open(os.path.join(here, "BENCH_SWEEP.json")) as f:
                    results = results + [
                        r for r in json.load(f).get("results", [])
                        if str(r.get("coll", "")).startswith(prefix)]
            except (OSError, ValueError):
                pass
    payload = {"ndev": ndev, "results": results}
    if mfu:
        payload["mfu"] = mfu
    _atomic_write(os.path.join(here, "BENCH_SWEEP.json"),
                  json.dumps(payload, indent=1))
    lines = ["# Collective sweep (OSU protocol, BASELINE.md configs "
             "#1-#5)", ""]
    lines += [f"Devices: {ndev}", ""] + _table(
        [r for r in results
         if not str(r.get("coll", "")).startswith(("serving_",
                                                   "recovery_",
                                                   "quant_"))])
    if mfu:
        lines += ["", "## Single-chip MFU", ""]
        for r in mfu:
            mfu_s = (f"{r['mfu'] * 100:.1f}% of "
                     f"{r['peak_tflops_assumed']} TF peak")
            extra = (f", {r['vs_jnp_speedup']}x vs jnp"
                     if "vs_jnp_speedup" in r else "")
            lines.append(f"- `{r['metric']}` [{r['grade']}]: "
                         f"{r['tflops']} TFLOP/s ({mfu_s}){extra}")
    if multidev_rows:
        lines += ["", "## 8 virtual CPU devices (correctness-grade)",
                  "",
                  "Framework-vs-raw ratios on an 8-device CPU mesh: "
                  "dispatch + algorithm-choice regressions show up "
                  "here without pod access.  NOT bandwidth numbers.",
                  ""] + _table(multidev_rows)
    serving_now = [r for r in results
                   if str(r.get("coll", "")).startswith("serving_")]
    if serving_now:
        lines += _serving_md_section(serving_now)
    recovery_now = [r for r in results
                    if str(r.get("coll", "")).startswith("recovery_")]
    if recovery_now:
        lines += _recovery_md_section(recovery_now)
    quant_now = [r for r in results
                 if str(r.get("coll", "")).startswith("quant_")]
    if quant_now:
        lines += _quant_md_section(quant_now)
    _atomic_write(os.path.join(here, "BENCH_SWEEP.md"),
                  "\n".join(lines) + "\n")


def _pallas_first_run(devs, mesh, interp: bool) -> dict:
    """coll/pallas validation: every ring-kernel variant executes on
    THIS mesh (compiled on real TPU, interpreter elsewhere) and matches
    numpy.  Each kernel is named before it runs (a hang shows where)
    and a kernel that raises is reported with the compiler's or
    runtime's message and recorded False — the rest still run."""
    import jax

    from ompi_tpu.ops import pallas_collectives as pc
    from ompi_tpu.ops import pallas_overlap as po

    n = len(devs)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 256)).astype(np.float32)
    x2 = rng.standard_normal((n, n, 16)).astype(np.float32)
    # Mosaic refuses (the interpreter does not) all-gather blocks that
    # are not (8, 128) tiles, all-to-all blocks and fused-GEMM outputs
    # narrower than 128 lanes, and a bf16 wire under n*8*128 elements
    xt = rng.standard_normal((n, 8, 128)).astype(np.float32)
    x2t = rng.standard_normal((n, n, 128)).astype(np.float32)
    xw = rng.standard_normal((n, n * 8 * 128)).astype(np.float32)
    put = jax.device_put
    checks = {}

    def chk(name, run, want, tol=1e-4, same=None):
        print(f"pallas first run: {name} ...", file=sys.stderr, flush=True)
        try:
            got = np.asarray(run())
            checks[name] = bool(same(got) if same is not None else
                                np.allclose(got, want, atol=tol, rtol=tol))
        except Exception as exc:
            msg = " ".join(str(exc).split())
            print(f"pallas first run: {name} RAISED "
                  f"{type(exc).__name__}: {msg[:600]}", file=sys.stderr,
                  flush=True)
            checks[name] = False

    def ar(op="sum", src=x, **kw):
        return lambda: pc.all_reduce(put(src), mesh, "x", op,
                                     interpret=interp, **kw)

    chk("allreduce_fused", ar(), x.sum(0))
    chk("allreduce_seg", ar(variant="seg", seg_elems=64), x.sum(0))
    chk("allreduce_bidi", ar(variant="bidi"), x.sum(0))
    chk("allreduce_seg_bidi", ar(variant="seg_bidi", seg_elems=32),
        x.sum(0))
    chk("allreduce_max", ar("max"), x.max(0), tol=1e-6)
    chk("allreduce_wire16", ar(src=xw, variant="wire16"), xw.sum(0),
        tol=0.25)
    chk("reduce_scatter",
        lambda: pc.reduce_scatter(put(x2), mesh, "x", "sum",
                                  interpret=interp), x2.sum(0))
    chk("allgather",
        lambda: pc.all_gather(put(xt), mesh, "x", interpret=interp),
        xt, tol=1e-6)
    chk("allgather_bidi",
        lambda: pc.all_gather(put(xt), mesh, "x", interpret=interp,
                              variant="bidi"), xt, tol=1e-6)
    chk("bcast",
        lambda: pc.bcast(put(x), mesh, "x", root=1, interpret=interp),
        np.broadcast_to(x[1], x.shape), tol=1e-6)
    chk("alltoall",
        lambda: pc.all_to_all(put(x2t), mesh, "x", interpret=interp),
        np.swapaxes(x2t, 0, 1), tol=1e-6)
    xv = rng.standard_normal((n, n, 8, 128)).astype(np.float32)
    cnt = rng.integers(1, 9, (n, n)).astype(np.int32)
    chk("alltoallv_ragged",
        lambda: pc.all_to_all_v(put(xv), cnt, mesh, "x",
                                interpret=interp), None,
        same=lambda got: all(
            np.array_equal(got[j, i, :cnt[i, j]], xv[i, j, :cnt[i, j]])
            for i in range(n) for j in range(n)))
    if n % 2 == 0 and n >= 4:
        from jax.sharding import Mesh

        mesh2 = Mesh(np.asarray(devs).reshape(2, n // 2), ("x", "y"))
        chk("allreduce_torus",
            lambda: pc.all_reduce_torus(
                put(x.reshape(2, n // 2, -1)), mesh2, ("x", "y"),
                interpret=interp), x.sum(0))
        chk("reduce_scatter_torus",
            lambda: pc.reduce_scatter_torus(put(x2), mesh2, ("x", "y"),
                                            interpret=interp), x2.sum(0))
        chk("allgather_torus",
            lambda: pc.all_gather_torus(put(x), mesh2, ("x", "y"),
                                        interpret=interp), x, tol=1e-6)

    # the fused compute+communicate kernels are part of the evidence
    # set too (pallas_overlap: new collective_ids, real RDMA semantics
    # on hardware)
    m, k_loc, n_out = 8 * n, 16, 128
    a = rng.standard_normal((n, m, k_loc)).astype(np.float32)
    bb = rng.standard_normal((n, k_loc, n_out)).astype(np.float32)
    want = sum(a[i] @ bb[i] for i in range(n))
    chk("matmul_allreduce",
        lambda: po.matmul_allreduce(put(a), put(bb), mesh, "x",
                                    interpret=interp), want, tol=1e-3)
    chk("matmul_reduce_scatter",
        lambda: po.matmul_reduce_scatter(put(a), put(bb), mesh, "x",
                                         interpret=interp),
        want.reshape(n, m // n, n_out), tol=1e-3)
    return checks


def _ladder_row(coll: str, variant: str, nbytes: int, xla_us: float,
                pallas_us: float, interp: bool) -> dict:
    """One LADDER_PROBE row.  Interpreter-grade timings misrepresent
    the pallas/xla crossover by 10-25x (the interpreter serializes what
    hardware overlaps), so dryrun rows carry ``binding: false`` and NO
    winner — a decision ladder seeded from them would permanently gate
    pallas off.  Only device-grade rows declare one."""
    row = {"coll": coll, "variant": variant, "nbytes": nbytes,
           "xla_us": xla_us, "pallas_us": pallas_us,
           "binding": not interp}
    row["winner"] = (None if interp
                     else ("pallas" if pallas_us < xla_us else "xla"))
    return row


def _ladder_probe(b: "DeviceBench", interp: bool, sizes,
                  failed: list) -> list:
    """Tuned-ladder re-derivation scaffold: per (size, variant), the
    compiler-scheduled coll/xla path vs the explicit coll/pallas ring —
    the measurement the device ladder's crossovers are derived from on
    a real pod.  Both the fused and segmented variants are probed (the
    fused/seg crossover is itself a ladder input).  Timings use the
    shared interleaved ``_timed_pair`` protocol (drift hits both sides
    of a pair equally); interpreter-mode runs are dryrun-grade.
    """
    from ompi_tpu.ops import pallas_collectives as pc
    from ompi_tpu.ops import pallas_overlap as po

    rows = []
    for nbytes in sizes:
        x = b.make(nbytes)
        variants = ["fused"] if nbytes < (64 << 10) else ["fused", "seg"]
        for variant in variants:
            def pallas_fn(t, variant=variant):
                return pc.all_reduce(t, b.mesh, "x", "sum",
                                     interpret=interp, variant=variant)

            pair = b._timed_pair(f"ladder_{variant}", b.fw_fn("allreduce"),
                                 pallas_fn, x, x, nbytes, iters=6)
            rows.append(_ladder_row("allreduce", variant, nbytes,
                                    pair["fw_lat_us"],
                                    pair["raw_lat_us"], interp))

    # bcast + alltoall crossovers: the other slots coll/pallas can own
    for coll in ("bcast", "alltoall"):
        nbytes = 262144
        if coll == "bcast":
            x = b.make(nbytes)

            def pallas_coll_fn(t):
                return pc.bcast(t, b.mesh, "x", root=0,
                                interpret=interp)
        else:
            nelem = max(b.ndev, nbytes // 4 // b.ndev * b.ndev)
            x = b.xla_mod.make_world_array(np.ones(
                (b.world.size, b.ndev, nelem // b.ndev), np.float32))

            def pallas_coll_fn(t):
                return pc.all_to_all(t, b.mesh, "x", interpret=interp)

        pair = _attempt(
            failed, f"ladder_{coll}",
            lambda: b._timed_pair(f"ladder_{coll}", b.fw_fn(coll)
                                  if coll == "bcast"
                                  else (lambda t: b.world
                                        .alltoall_array(t)),
                                  pallas_coll_fn, x, x, nbytes, iters=6))
        if pair is not None:
            rows.append(_ladder_row(coll, "ring", nbytes,
                                    pair["fw_lat_us"],
                                    pair["raw_lat_us"], interp))

    # fused collective matmul vs XLA's matmul-then-psum: the overlap row
    # the explicit transport exists for (ops/pallas_overlap.py)
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = b.ndev
    M = K = 256
    N = 128
    key_a = jnp.ones((n, M, K // n), jnp.float32)
    key_b = jnp.ones((n, K // n, N), jnp.float32)

    def fused(args):
        return po.matmul_allreduce(args[0], args[1], b.mesh, "x",
                                   interpret=interp)

    unfused = jax.jit(shard_map(
        lambda a, bb: jax.lax.psum(a[0] @ bb[0], "x"),
        mesh=b.mesh, in_specs=(P("x"), P("x")), out_specs=P(),
        check_vma=False))

    pair = b._timed_pair(
        "ladder_matmul", fused, lambda args: unfused(*args),
        (key_a, key_b), (key_a, key_b), M * K * 4, iters=6)
    rows.append(_ladder_row("matmul_allreduce", "overlap", M * K * 4,
                            pair["raw_lat_us"], pair["fw_lat_us"],
                            interp))
    return rows


def _pallas_aot_gate(here: str) -> dict:
    """Pre-gate: AOT-compile every coll/pallas kernel for a real TPU
    topology (no hardware needed — libtpu's Mosaic compiler runs
    offline).  A kernel failing here would fail on a live pod, so the
    device sweep shouldn't bother until this is green.

    Runs in a CPU-pinned subprocess BEFORE this process touches jax:
    once the parent holds the chip, libtpu refuses the compile-only
    child too ("Internal error when accessing libtpu multi-process
    lockfile", seen on the v5e).  So the gate cannot wait for
    ``require_tpu``: without a TPU, pod-smoke still rewrites
    PALLAS_AOT.json — an offline compile record, true of any machine —
    before it exits non-zero."""
    import importlib.util

    if importlib.util.find_spec("libtpu") is None:
        # no offline Mosaic compiler on this machine: the gate cannot
        # run, which is NOT a compile failure (the CI test skips on the
        # same condition) — report skipped, don't fail pod-smoke
        print("pod-smoke: pallas AOT gate skipped (no libtpu)",
              file=sys.stderr)
        return {"skipped": True, "reason": "libtpu unavailable"}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = os.path.join(here, "PALLAS_AOT.json")
    try:
        # a crashed run must not report green off a previous run's file
        try:
            os.remove(out)
        except FileNotFoundError:
            pass
        proc = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.tools.pallas_aot",
             "--out", out],
            cwd=here, env=env, capture_output=True, text=True,
            timeout=900)
        if proc.returncode not in (0, 1) or not os.path.exists(out):
            # rc 1 = compiled-with-failures (the file says which); any
            # other rc means the gate itself crashed
            raise RuntimeError(
                f"pallas_aot rc={proc.returncode}: "
                f"{proc.stderr[-400:]}")
        res = json.loads(open(out).read())
        summary = {"ok": res.get("ok", False),
                   "n_compiled": res.get("n_compiled", 0),
                   "n_kernels": res.get("n_kernels", 0),
                   "topology": res.get("topology")}
        print(f"pod-smoke: pallas AOT {summary['n_compiled']}/"
              f"{summary['n_kernels']} kernels compiled for "
              f"{summary['topology']}")
        return summary
    except Exception as exc:
        print(f"pod-smoke: pallas AOT gate failed: {exc}",
              file=sys.stderr)
        return {"ok": False, "error": str(exc)[:300]}


def pod_smoke(dry_run: bool = False) -> int:
    """One-command pod readiness (SURVEY §6 measurement protocol): the
    first hour of real multi-chip access runs THIS to produce the full
    round's evidence set instead of ad-hoc commands.

    Phases: (1) Mosaic AOT gate (CPU child, before this process opens
    the chip), (2) coll/pallas first-run validation of every
    ring-kernel variant, (3) the canonical full sweep via main() — IN
    THIS PROCESS, which holds the chip — or a mini-sweep (dry run),
    (4) tuned-ladder re-derivation probe -> LADDER_PROBE.json.
    ``--dry-run`` forces the 8-virtual-CPU mesh + interpreter kernels
    so CI can validate the script itself; without it a TPU is required.
    """
    here = os.path.dirname(os.path.abspath(__file__))
    report = {"dry_run": dry_run, "phases": {}}
    report["phases"]["pallas_aot"] = _pallas_aot_gate(here)
    if dry_run:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devs = jax.devices() if dry_run else require_tpu("bench")
    platform = devs[0].platform
    report["phases"]["probe"] = {"ok": True, "ndev": len(devs),
                                 "platform": platform}
    print(f"pod-smoke: {len(devs)} {platform} device(s)")

    from jax.sharding import Mesh

    mesh = Mesh(np.array(devs), ("x",))
    checks = _pallas_first_run(devs, mesh, interp=dry_run)
    report["phases"]["pallas_first_run"] = {
        "interpret": dry_run, **checks}
    print("pod-smoke: pallas kernels "
          + ("ALL OK" if all(checks.values()) else f"FAILED: {checks}"))

    failed: list = []
    b = DeviceBench()
    if dry_run:
        rows = [b.point("allreduce", nb, iters=6)
                for nb in MULTIDEV_SIZES]
        rows.append(b.persistent_point(MULTIDEV_SPOT, iters=10))
        report["phases"]["sweep"] = {"grade": "dryrun", "rows": rows}
    ladder = _ladder_probe(b, dry_run, sizes=(4096, 262144, 4 << 20),
                           failed=failed)
    grade = "dryrun" if dry_run else "device"
    _atomic_write(os.path.join(here, "LADDER_PROBE.json"),
                  json.dumps({"grade": grade, "rows": ladder}, indent=1))
    report["phases"]["ladder_probe"] = {"grade": grade,
                                        "rows": len(ladder)}
    aot = report["phases"]["pallas_aot"]
    ok_all = (all(checks.values()) and not failed
              and (aot.get("ok", False) or aot.get("skipped", False)))
    if not dry_run:
        # the canonical sweep + driver metric line, in this process
        # (init is idempotent).  The report records what actually
        # happened and is written AFTER, so a failed sweep can't leave
        # a report claiming device-grade evidence never produced.
        rc = main()
        report["phases"]["sweep"] = {"grade": "device", "ok": rc == 0,
                                     "via": "main() full sweep"}
        ok_all = ok_all and rc == 0
    _atomic_write(os.path.join(here, "POD_SMOKE.json"),
                  json.dumps(report, indent=1, default=str))
    if dry_run:
        import ompi_tpu

        ompi_tpu.finalize()    # main() finalizes on the device path
    print(f"pod-smoke: {'READY' if ok_all else 'NOT READY'} "
          f"(report: POD_SMOKE.json, ladder: LADDER_PROBE.json)")
    return 0 if ok_all else 2


def main() -> int:
    """The device sweep, in ONE process — the one that holds the chip.
    Without a TPU it exits non-zero before measuring or writing
    anything.  A row that raises fails the run, after the rows already
    measured are flushed to the sweep files."""
    devs = require_tpu("bench")
    compile_cache_dir()
    fast = os.environ.get("OTPU_BENCH_FAST", "") not in ("", "0")
    failed: list = []
    b = DeviceBench()
    # the contract size first, then small -> large
    plan = [("allreduce", PRIMARY, 40)]
    if not fast:
        plan += [("allreduce", nb, 10) for nb in sorted(SWEEP_SIZES)
                 if nb != PRIMARY]
        for coll in ("bcast", "allgather", "reduce_scatter"):
            plan += [(coll, nb, 10) for nb in sorted(SPOT_SIZES)]
    rows = []
    for coll, nbytes, iters in plan:
        r = _attempt(failed, f"{coll}@{nbytes}",
                     lambda: b.point(coll, nbytes, iters=iters))
        if r is not None:
            rows.append(r)
    mfu = []
    if not fast:
        mfu = mfu_rows(failed)
        r = _attempt(failed, "allreduce_persistent",
                     lambda: b.persistent_point(PRIMARY))
        if r is not None:
            rows.append(r)
        # the host rows and the 8-virtual-CPU table come from
        # CPU-pinned children: none of them needs the chip this
        # process holds
        write_sweep(b.ndev, rows + host_rows(), multidev_sweep(), mfu=mfu)
    primary = next((r for r in rows if r["coll"] == "allreduce"
                    and r["nbytes"] == PRIMARY), None)
    if primary is not None:
        emit_metric(primary["fw_bw_gbs"], primary["ratio"], devs)
    import ompi_tpu

    ompi_tpu.finalize()
    if failed:
        print(f"bench: {len(failed)} device row(s) FAILED: "
              f"{', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    if "--multidev-child" in sys.argv:
        multidev_child()
    elif "--multidev" in sys.argv:
        for row in multidev_sweep():
            print(row)
    elif "--reactor-history" in sys.argv:
        for row in reactor_history_rows(
                native="--baseline" not in sys.argv):
            print(json.dumps(row))
    elif "--history" in sys.argv:
        for row in history_rows():
            print(json.dumps(row))
    elif "--ladder" in sys.argv:
        for row in ladder_host_rows():
            print(json.dumps(row))
    elif "--serving" in sys.argv:
        for row in refresh_serving_tables():
            print(json.dumps(row))
    elif "--recovery" in sys.argv:
        for row in refresh_recovery_tables():
            print(json.dumps(row))
    elif "--quant" in sys.argv:
        for row in refresh_quant_tables():
            print(json.dumps(row))
    elif "--moe" in sys.argv:
        for row in refresh_moe_tables():
            print(json.dumps(row))
    elif "--pod-smoke" in sys.argv:
        sys.exit(pod_smoke(dry_run="--dry-run" in sys.argv))
    elif "--mfu" in sys.argv:
        mfu_failed: list = []
        for row in mfu_rows(mfu_failed):
            print(json.dumps(row))
        sys.exit(1 if mfu_failed else 0)
    else:
        sys.exit(main())
