"""Host-path regression guards, as counts.

Round-2 review found `allreduce_host_tuned` collapsing superlinearly at
4MB (~12x worse per byte than the 256KB point on the 1-core VM).  The
fixes (escalating idle backoff + doorbell wakeups, header/payload split
frames, contiguous-datatype fast paths, zero-copy eager sends,
scratch-buffer reuse) are pinned here by what the datapath counts, not
by a clock: messages and bytes a call puts on the wire, payload copies,
"disabled means nothing recorded" identities.  Speed is measured on the
chip (PERF.md, PERF_LEDGER.jsonl); no assertion in this file reads a
wall clock.  Mirrors the linear volume of the reference's ring
(``coll_base_allreduce.c:341``).
"""
import json
import os
import subprocess
import sys
import textwrap

import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np, ompi_tpu
    from ompi_tpu.runtime import spc

    KEYS = ("isend", "bytes_sent", "bytes_packed",
            "fastpath_payload_copies")
    w = ompi_tpu.init()
    out = []
    for nbytes in (262144, 4194304):
        x = np.ones(nbytes // 4, np.float32)
        w.allreduce(x)                     # warm: schedule, scratch
        w.barrier()
        c0 = spc.counters()
        y = w.allreduce(x)
        c1 = spc.counters()
        assert (np.asarray(y) == w.size).all()
        out.append([c1.get(k, 0) - c0.get(k, 0) for k in KEYS])
    print(f"GUARD{w.rank} " + json.dumps(out))
    ompi_tpu.finalize()
""")


def test_allreduce_per_byte_cost_stays_linear(tmp_path):
    """16x the bytes may put at most 16x the bytes and 16x the messages
    on the wire, and copy no payload: the round-2 pathology as a count.
    ^sm_coll isolates the tuned ladder (on one host coll/sm owns
    sub-slot payloads and sends nothing through the pml)."""
    script = tmp_path / "guard.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "4",
         "--mca", "coll", "^sm_coll", sys.executable, str(script)],
        240, env)
    assert r.returncode == 0, r.stdout + r.stderr
    for rank in range(4):
        line = next(ln for ln in r.stdout.splitlines()
                    if f"GUARD{rank} " in ln)
        small, big = json.loads(line.split(f"GUARD{rank} ", 1)[1])
        s_msgs, s_bytes, s_packed, s_copies = small
        b_msgs, b_bytes, b_packed, b_copies = big
        assert s_msgs >= 1 and s_bytes >= 262144, (rank, small)
        # a ring moves 2(n-1)/n x S a rank: 6 messages, 1.5 x 4MB here
        assert b_bytes <= 16 * s_bytes, (rank, small, big)
        assert b_msgs <= 16 * s_msgs, (rank, small, big)
        # contiguous float32: nothing is packed or copied at either size
        assert (s_packed, s_copies, b_packed, b_copies) == (0, 0, 0, 0), \
            (rank, small, big)


_FASTPATH_COPY_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np, ompi_tpu
    from ompi_tpu.runtime import spc

    w = ompi_tpu.init()
    # contiguous eager messages over btl/tcp (fake-nodes forces tcp),
    # ping-ponged so the socket never backpressures: the zero-copy
    # contract says the user buffer's view rides to sendmsg with NO
    # intermediate payload copy
    x = np.ones(16 << 10, np.uint8)
    y = np.empty_like(x)
    for i in range(50):
        if w.rank == 0:
            w.send(x, dest=1, tag=1)
            w.recv(y, source=1, tag=2)
        else:
            w.recv(y, source=0, tag=1)
            w.send(x, dest=1 - w.rank, tag=2)
    c = spc.counters()
    print(f"COPYPIN{w.rank} " + json.dumps(
        [c.get("fastpath_payload_copies", -1),
         c.get("fastpath_hdr_fast", -1),
         c.get("fastpath_hdr_pickle", -1)]))
    ompi_tpu.finalize()
""")


_SCHED_CACHE_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np, ompi_tpu
    from ompi_tpu.runtime import spc

    w = ompi_tpu.init()
    big = np.ones(65536, np.float32)      # 256KB: above the eager lane
    small = np.ones(256, np.float32)      # 1KB: eager lane
    w.allreduce(big)
    base_hits = spc.read("fastpath_sched_hits")
    w.allreduce(big)                      # identical second call
    hits_after = spc.read("fastpath_sched_hits")
    w.allreduce(small)
    if w.rank == 0:
        print("SCHEDPIN " + json.dumps(
            [base_hits, hits_after,
             spc.read("fastpath_eager_lane")]))
    ompi_tpu.finalize()
""")


def test_fastpath_zero_copy_tcp_send(tmp_path):
    """The fastpath acceptance pin: on the contiguous tcp send path the
    payload must never be copied (SPC ``fastpath_payload_copies`` == 0
    — the sender's memoryview rides to sendmsg) and the fixed fast
    header must carry the data frames (pickle only for the handshake's
    exotic frames)."""
    script = tmp_path / "copy_pin.py"
    script.write_text(_FASTPATH_COPY_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "2",
         "--fake-nodes", "2", sys.executable, str(script)],
        240, env)
    assert r.returncode == 0, r.stdout + r.stderr
    for rank in (0, 1):
        line = next(ln for ln in r.stdout.splitlines()
                    if f"COPYPIN{rank}" in ln)
        copies, fast, pickle_h = json.loads(
            line.split(f"COPYPIN{rank} ", 1)[1])
        assert copies == 0, (
            f"rank {rank}: {copies} payload copies on the contiguous "
            f"tcp send path (zero-copy contract broken)")
        assert fast >= 50, f"rank {rank}: only {fast} fast headers"


def test_tuned_schedule_cache_hits_on_second_call(tmp_path):
    """coll/tuned decision+schedule caching: the second identical
    allreduce must hit the cached pick (SPC ``fastpath_sched_hits``
    grows), and a small allreduce must take the SPC-counted eager
    lane.  ^sm_coll isolates the tuned ladder (on one host coll/sm owns
    sub-slot payloads)."""
    script = tmp_path / "sched_pin.py"
    script.write_text(_SCHED_CACHE_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "4",
         "--mca", "coll", "^sm_coll", sys.executable, str(script)],
        240, env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines() if "SCHEDPIN" in ln)
    base_hits, hits_after, lane = json.loads(
        line.split("SCHEDPIN ", 1)[1])
    assert hits_after > base_hits, (
        f"second identical allreduce did not hit the schedule cache "
        f"({base_hits} -> {hits_after})")
    assert lane >= 1, "small allreduce skipped the eager lane"


def test_sanitizer_off_zero_overhead():
    """OTPU_SANITIZE off must cost the 4KB eager lane NOTHING: the
    @hot_path decorator is identity (no wrapper object on any tagged hot
    function — the strongest possible zero-overhead proof), the
    memchecker hook stays dormant, and the sanitizer flag is a module
    bool no hot path reads outside its cold branches."""
    from ompi_tpu.datatype.convertor import Convertor
    from ompi_tpu.mca.accelerator.jax_acc import _StagingPool
    from ompi_tpu.mca.btl.tcp import TcpBtl
    from ompi_tpu.mca.coll.tuned import TunedModule
    from ompi_tpu.runtime import hotpath, memchecker, progress, sanitizer

    assert sanitizer.enabled is False          # default off
    assert memchecker.enabled() is False       # hook dormant

    def f():
        return 1

    assert hotpath.hot_path(f) is f            # decorator is identity
    # every tagged hot function is the plain function object — no
    # wrapper, no __wrapped__, nothing to pay per call
    for fn in (TcpBtl.send, TcpBtl._flush_locked, TcpBtl._on_bytes,
               TunedModule.allreduce, Convertor.pack_borrow,
               _StagingPool.acquire, _StagingPool.release,
               progress.progress):
        assert not hasattr(fn, "__wrapped__"), fn
    # the registry recorded the eager-lane path's hot functions
    regs = hotpath.registered()
    for qual in ("TcpBtl.send", "TunedModule.allreduce",
                 "Convertor.pack_borrow", "_StagingPool.acquire"):
        assert any(q.endswith(qual) for q in regs), qual


def test_reactor_off_zero_overhead():
    """otpu_progress_native=0 must be IDENTITY: no reactor thread, no
    handle, no drain callback on the progress tick path, and drain()
    itself is a pure-Python two-load early return (no ctypes call ever
    fires).  The fallback selector lane in btl/tcp is the same code
    that shipped before the reactor existed."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import progress, reactor, spc

    var = registry.lookup("otpu_progress_native")
    saved = var.value
    var.set(False)
    try:
        assert not reactor.configured()
        assert not reactor.engage()              # declines, no side effects
        assert reactor._handle == 0              # no native object
        assert not reactor.active()
        with progress._lock:
            assert reactor.drain not in progress._callbacks
            assert reactor.drain not in progress._lp_callbacks
        spc.init()
        before = (spc.read("progress_native_drains"),
                  spc.read("fastpath_native_frags"))
        assert reactor.drain() == 0              # early return, no ctypes
        assert (spc.read("progress_native_drains"),
                spc.read("fastpath_native_frags")) == before
    finally:
        var.set(saved)
        progress.reset_for_testing()


def test_weave_off_zero_overhead():
    """With no weave run active (the production state — OTPU_SANITIZE
    off, no explorer), the interleaving instrumentation must cost the
    lock layer NOTHING: no run object exists, instrument() returns its
    argument with every _guarded_by lock attribute untouched (a plain
    threading primitive — no wrapper on Lock acquire), make_lock hands
    back a plain RLock, and pause/signal are immediate returns."""
    import threading

    from ompi_tpu.analysis import weave
    from ompi_tpu.mca.accelerator.jax_acc import _StagingPool
    from ompi_tpu.runtime import sanitizer

    assert sanitizer.enabled is False
    assert weave.active() is None
    pool = _StagingPool(max_bytes=1 << 20, enabled=True)
    lock_before = pool._lock
    assert weave.instrument(pool) is pool
    assert pool._lock is lock_before
    # the plain runtime lock type, not a WeaveLock wrapper: acquire is
    # the raw C primitive
    assert isinstance(pool._lock, type(threading.RLock()))
    assert not isinstance(pool._lock, weave.WeaveLock)
    assert isinstance(weave.make_lock("x"), type(threading.RLock()))
    weave.pause("never")             # no-ops, no run to yield into
    weave.signal("never")
    assert weave.active() is None


def test_chaos_disabled_zero_overhead():
    """An empty otpu_chaos_spec must cost the wire NOTHING: chaos is a
    module bool the hot paths read in one cold branch (the
    trace/sanitizer discipline), no engine exists, the frame checksum
    stays unarmed, and every hook is an immediate return."""
    from ompi_tpu.ft import chaos
    from ompi_tpu.mca.btl import tcp as tcp_mod
    from ompi_tpu.runtime import spc

    assert chaos.enabled is False              # default off
    assert chaos._engine is None               # nothing armed
    assert tcp_mod._cksum_armed() is False     # no crc on the wire
    # every hook is inert without an engine — no draws, no counters
    before = {k: spc.read(k) for k in
              ("chaos_drop", "chaos_delay", "chaos_dup", "chaos_corrupt",
               "chaos_reset", "chaos_stall", "chaos_disconnect",
               "chaos_kill")}
    assert chaos.wire_send("tcp", True) is None
    assert chaos.wire_recv("tcp", True) is None
    assert chaos.coord_stall("put") is None
    assert chaos.coord_disconnect("put") is False
    chaos.kill_point("step", n=0)
    assert {k: spc.read(k) for k in before} == before
    # install/uninstall restores the zero-cost identity
    chaos.install_spec("delay:ms=1,p=1", rank=0)
    assert chaos.enabled is True
    chaos.uninstall()
    assert chaos.enabled is False and chaos._engine is None
    assert tcp_mod._cksum_armed() is False


def test_small_pack_skips_pool_dispatch(monkeypatch):
    """fastpath satellite: packs below ``_POOL_PACK_MIN`` must never
    reach the worker pool — the threads_pool_pack_4MB bench measured
    pool dispatch barely breaking even at 4MB, so sub-threshold packs
    keep the serial native loop with zero pool traffic."""
    import numpy as np

    from ompi_tpu.datatype import convertor as conv_mod
    from ompi_tpu.datatype import core as dt_core
    from ompi_tpu.mca.threads import base as threads_base

    # the threshold itself is part of the contract
    assert conv_mod._POOL_PACK_MIN >= (1 << 21), \
        "parallel-pack fan-out threshold regressed below 2MB"
    calls = []
    monkeypatch.setattr(threads_base, "get_pool",
                        lambda: calls.append(1))
    vec = dt_core.vector(2, 1, 2, dt_core.FLOAT32)
    n = (conv_mod._POOL_PACK_MIN // vec.size) - 1   # just under
    buf = np.zeros(n * (vec.extent // 4), np.float32)
    packed = conv_mod.Convertor(vec, n, buf).pack()
    assert packed.nbytes == n * vec.size
    assert not calls, "sub-threshold pack dispatched to the pool"


_TRACE_PIN_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np, ompi_tpu
    from ompi_tpu.api import op as op_mod
    from ompi_tpu.runtime import trace

    w = ompi_tpu.init()
    # conductor-world stacked layout: one 1KB row per hosted rank
    x = np.ones((w.size, 256), np.float32)
    wrapped = w.c_coll["allreduce"]          # trace wrapper (outermost)
    for _ in range(100):
        wrapped(w, x, op_mod.SUM)
    print("TRACEPIN " + json.dumps(
        [hasattr(wrapped, "__wrapped__"), trace.recorded_count(),
         len(trace.histograms())]))
    ompi_tpu.finalize()
""")


_PREADY_PIN_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np, ompi_tpu
    from ompi_tpu.base.var import registry
    from ompi_tpu.mca.part import part_framework
    from ompi_tpu.runtime import trace

    w = ompi_tpu.init()
    part_framework().open()
    # aggregation threshold above the partition count: every pready but
    # the last is pure bookkeeping (bitmap bit + run merge), isolating
    # the hot call from the wire send
    P = 512
    registry.set("otpu_part_persist_min_partitions", P + 1)
    a, b = w.as_rank(0), w.as_rank(1)
    x = np.zeros(P * 8, np.float32)
    y = np.zeros(P * 8, np.float32)
    s = a.psend_init(x, P, dest=1, tag=1)
    r = b.precv_init(y, P, source=0, tag=1)

    for _ in range(2):
        s.start(); r.start()
        for p in range(P):
            s.pready(p)
        s.wait(); r.wait()
    print("PREADYPIN " + json.dumps(
        [trace.recorded_count(), len(trace.histograms())]))
    ompi_tpu.finalize()
""")


_SESSION_PIN_SCRIPT = textwrap.dedent("""
    import json
    import ompi_tpu
    from ompi_tpu.runtime import trace
    from ompi_tpu import instance as inst_mod

    w = ompi_tpu.init()           # boots the instance ONCE (held by world)
    boot_inst = inst_mod.current()

    for _ in range(50):
        s = ompi_tpu.Session.init()
        s.finalize()
        assert inst_mod.current() is boot_inst   # never re-booted
    print("SESSIONPIN " + json.dumps(
        [trace.recorded_count(), len(trace.histograms())]))
    ompi_tpu.finalize()
""")


def test_session_acquire_disabled_path_cost(tmp_path):
    """Refcounted Session.init/finalize on an already-booted instance
    must be bookkeeping only: (a) no RTE re-boot (the same instance
    object after every cycle, asserted in the child), (b) zero
    otpu-trace events/histograms while tracing is disabled (the boot
    spans are enabled-path only)."""
    script = tmp_path / "session_pin.py"
    script.write_text(_SESSION_PIN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines() if "SESSIONPIN" in ln)
    recorded, hists = json.loads(line.split("SESSIONPIN ", 1)[1])
    assert recorded == 0, f"{recorded} trace events while disabled"
    assert hists == 0, f"{hists} histogram bins while disabled"


def test_pready_disabled_path_overhead(tmp_path):
    """The Pready hot call (one per gradient bucket per step in the
    overlap pattern) with tracing disabled must record nothing: zero
    trace events and histogram bins over two whole epochs."""
    script = tmp_path / "pready_pin.py"
    script.write_text(_PREADY_PIN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines() if "PREADYPIN" in ln)
    recorded, hists = json.loads(line.split("PREADYPIN ", 1)[1])
    assert recorded == 0, f"{recorded} trace events while disabled"
    assert hists == 0, f"{hists} histogram bins while disabled"


def test_tracing_disabled_overhead_is_one_flag_check(tmp_path):
    """The otpu-trace coll-table wrapper is installed unconditionally at
    comm_select; with tracing disabled (the default) its cost on the
    allreduce hot path must be one flag check — pinned as zero
    events/histograms recorded over calls through the wrapper."""
    script = tmp_path / "trace_pin.py"
    script.write_text(_TRACE_PIN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines() if "TRACEPIN" in ln)
    is_wrapped, recorded, hists = json.loads(
        line.split("TRACEPIN ", 1)[1])
    assert is_wrapped, "the coll table carries no trace wrapper"
    # the disabled path must not have recorded anything at all
    assert recorded == 0, f"{recorded} events recorded while disabled"
    assert hists == 0, f"{hists} histogram bins touched while disabled"


def test_flow_disabled_zero_overhead():
    """otpu-crit satellite pin: with ``otpu_trace_flow`` off (or
    tracing off entirely) the flow layer is an identity — flow_start/
    flow_finish record nothing, pml spans carry no flow key, requests
    never grow a _flow stamp, the coll wrapper allocates no cseq, and
    the SPC flow counters stay flat.  The record path must be byte-
    identical to the pre-otpu-crit tracer."""
    import numpy as np

    import ompi_tpu
    from ompi_tpu.base.var import registry as _registry
    from ompi_tpu.runtime import init as rt
    from ompi_tpu.runtime import spc, trace

    # default-off half: tracing disabled forces flow off whatever the
    # flow var says, and the flow calls are guarded no-ops
    _registry.set("otpu_trace_enable", False)
    trace.reset_for_testing()
    assert trace.flow_enabled is False
    before = spc.read("flow_starts"), spc.read("flow_finishes")
    trace.flow_start("pml_msg", (0, 0, 1, 0))
    trace.flow_finish("pml_msg", (0, 0, 1, 0))
    assert trace.recorded_count() == 0
    # tracing ON, flow OFF: spans record exactly the pre-flow shape
    rt.reset_for_testing()
    _registry.set("otpu_trace_enable", True)
    _registry.set("otpu_trace_flow", False)
    trace.reset_for_testing()
    try:
        assert trace.enabled is True and trace.flow_enabled is False
        w = ompi_tpu.init()
        x = np.ones(64, np.float32)
        buf = np.empty_like(x)
        a, b = w.as_rank(0), w.as_rank(1)
        sreq = a.isend(x, dest=1, tag=9)
        b.recv(buf, source=0, tag=9)
        sreq.wait()
        evs = trace.chrome_events()
        pml = [e for e in evs if e.get("cat") == "pml"]
        assert pml, "pml spans missing"
        for e in pml:
            assert "fid" not in (e.get("args") or {}), e
        assert not [e for e in evs if e["ph"] in ("s", "f")]
        # no request ever carried a flow stamp
        assert trace._coll_seq == {}
        assert (spc.read("flow_starts"),
                spc.read("flow_finishes")) == before
        # conductor world: collectives take a leading rank axis
        w.allreduce(np.ones((w.size, 4), np.float32))
        colls = [e for e in trace.chrome_events()
                 if e.get("cat") == "coll"]
        assert colls and all("cseq" not in (e.get("args") or {})
                             for e in colls)
    finally:
        _registry.set("otpu_trace_enable", False)
        _registry.set("otpu_trace_flow", True)
        trace.reset_for_testing()
        rt.reset_for_testing()


def test_requests_disabled_zero_overhead():
    """otpu-req satellite pin: with ``otpu_trace_requests`` off (the
    default) the request layer is an identity even while tracing is
    fully ON — a whole serving run emits no serve_req spans, no
    rid.hop flow halves, no rid keys anywhere in the trace, requests
    never grow the request-layer lifecycle stamps, and the req_*/slo_*
    SPC counters stay flat (SLO accounting is gated by its own target
    var, unset here)."""
    import threading

    import ompi_tpu
    from ompi_tpu.base.var import registry as _registry
    from ompi_tpu.runtime import init as rt
    from ompi_tpu.runtime import spc, trace

    rt.reset_for_testing()
    _registry.set("otpu_trace_enable", True)
    trace.reset_for_testing()
    try:
        assert trace.enabled is True and trace.requests_enabled is False
        w = ompi_tpu.init()
        from ompi_tpu.serving import (ContinuousBatchScheduler, Router,
                                      ShardWorker)
        from ompi_tpu.serving.driver import PoissonDriver

        before = (spc.read("req_traced"), spc.read("req_stages"),
                  spc.read("slo_goodput"), spc.read("slo_breaches"))
        workers = [ShardWorker(w.as_rank(r), router=0) for r in (1, 2)]
        threads = [threading.Thread(target=wk.serve, daemon=True)
                   for wk in workers]
        for t in threads:
            t.start()
        r = Router(w.as_rank(0),
                   scheduler=ContinuousBatchScheduler(
                       max_batch=4, max_batch_tokens=4096),
                   workers=[1, 2], decode_chunk=4)
        rep = PoissonDriver(rate_rps=800, n_requests=8,
                            seed=2).run(r, max_wall_s=60)
        r.shutdown()
        for t in threads:
            t.join(timeout=10)
        assert rep["requests"] == 8
        evs = trace.chrome_events()
        assert not [e for e in evs if e.get("cat") == "serve_req"]
        assert not [e for e in evs if e.get("ph") in ("s", "f")
                    and e.get("name") == "serve_req"]
        for e in evs:
            assert "rid" not in (e.get("args") or {}), e
        # the request-layer stamps never fired (admit/done stamp
        # unconditionally — they predate otpu-req; the three new
        # single-write stamps are requests-gated)
        for q in r.completed():
            assert q.dispatch_ns is None and q.decode_ns is None \
                and q.last_res_ns is None, q.rid
        assert (spc.read("req_traced"), spc.read("req_stages"),
                spc.read("slo_goodput"),
                spc.read("slo_breaches")) == before
    finally:
        _registry.set("otpu_trace_enable", False)
        trace.reset_for_testing()
        rt.reset_for_testing()


def test_telemetry_disabled_zero_overhead():
    """otpu-top satellite pin: with otpu_telemetry_interval_ms at its
    default (0), the telemetry plane is an identity — no sampler
    object, no thread, sources are one dict insert at component init,
    and nothing ever snapshots trace/SPC state (the chaos-disabled
    discipline)."""
    import threading

    from ompi_tpu.runtime import flight, telemetry

    assert telemetry.enabled is False            # default off
    assert telemetry._sampler is None            # no sampler object
    assert not [t for t in threading.enumerate()
                if t.name == "otpu-telemetry"], "sampler thread exists"

    # start() without an interval (or without a coord client) stays off
    class _NoClientRte:
        client = None
        my_world_rank = 0

    assert telemetry.start(_NoClientRte()) is False
    assert telemetry.enabled is False and telemetry._sampler is None
    # the flight recorder is likewise inert until armed: dump() with no
    # armed RTE is a no-op returning None, whatever the enable var says
    flight.reset_for_testing()
    assert flight.dump("abort", detail="not armed") is None
    # registered sources are bookkeeping only — nothing calls them
    calls = []
    telemetry.register_source("tcp", lambda: calls.append(1))
    try:
        assert not calls
    finally:
        telemetry.unregister_source("tcp")
    # an undeclared source name is rejected loudly
    import pytest as _pytest

    with _pytest.raises(ValueError):
        telemetry.register_source("not_in_schema", dict)


def test_frontdoor_disabled_zero_overhead():
    """Front-door satellite pin: with no FrontDoor constructed the
    admission plane is an identity — module bool off, no armed
    instance, no thread ever (the door pumps on the fleet tick even
    when armed), the router completion hook is one module-attribute
    check, speculative decoding defaults off, and the
    serve_shed/serve_preempt SPC counters stay EXACTLY flat."""
    import threading

    from ompi_tpu.runtime import spc
    from ompi_tpu.serving import frontdoor
    from ompi_tpu.serving.worker import _spec_k_var

    assert frontdoor.enabled is False            # default off
    assert frontdoor._active is None             # no armed instance
    assert not [t for t in threading.enumerate()
                if "frontdoor" in t.name.lower()], "door thread exists"
    shed0 = spc.read("serve_shed")
    pre0 = spc.read("serve_preempt")
    # the module observe() hook with no door armed is a no-op
    frontdoor.observe("pool", "interactive", 5.0)
    frontdoor.observe("pool", "batch", 5.0)
    assert spc.read("serve_shed") == shed0
    assert spc.read("serve_preempt") == pre0
    # disarm without a door is likewise inert
    frontdoor.disarm()
    assert frontdoor.enabled is False and frontdoor._active is None
    # speculative decoding is off by default: otpu_serving_spec_k=0
    # means one target pass per token, draft model never consulted
    assert int(_spec_k_var.value or 0) == 0


_TELEMETRY_PIN_SCRIPT = textwrap.dedent("""
    import json, os, time
    from ompi_tpu.rte.coord import CoordServer

    srv = CoordServer(1)
    os.environ["OTPU_COORD"] = f"{srv.addr[0]}:{srv.addr[1]}"
    os.environ["OTPU_RANK"] = "0"
    os.environ["OTPU_NPROCS"] = "1"

    import numpy as np, ompi_tpu
    from ompi_tpu.api import op as op_mod
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import init as rt
    from ompi_tpu.runtime import spc, telemetry

    w = ompi_tpu.init()
    x = np.ones(1024, np.float32)               # the 4KB hot loop

    registry.lookup("otpu_telemetry_interval_ms").set(50)
    # the sampler publishes on its own thread while the 4KB hot loop
    # runs; the deadline is for a sampler that never publishes, not a
    # bound on speed
    telemetry.start(rt.get_rte())
    deadline = time.monotonic() + 30
    while (spc.read("telemetry_samples") < 1
           and time.monotonic() < deadline):
        w.allreduce(x, op_mod.SUM)
    telemetry.stop()
    samples = spc.read("telemetry_samples")
    print("TELEPIN " + json.dumps([samples]))
    ompi_tpu.finalize()
    srv.close()
""")


def test_telemetry_enabled_sampler_publishes(tmp_path):
    """The enabled-sampler pin: at a 50ms interval the sampler touches
    NO hot path (it snapshots counters on its own thread); while the
    4KB allreduce loop runs it must actually have sampled."""
    script = tmp_path / "tele_pin.py"
    script.write_text(_TELEMETRY_PIN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines() if "TELEPIN" in ln)
    (samples,) = json.loads(line.split("TELEPIN ", 1)[1])
    assert samples >= 1, "sampler never published a sample"


def test_profile_disabled_zero_overhead():
    """otpu-prof satellite pin: with otpu_profile_stages off and
    otpu_profile_interval_ms at its default (0), the profile plane is
    an identity — no sampler thread/object, no stage state ever
    recorded (not even mark objects for bogus names), and the
    instrumented datapath functions stay the plain @hot_path-unwrapped
    function objects."""
    import threading

    from ompi_tpu.datatype.convertor import Convertor
    from ompi_tpu.mca.accelerator.jax_acc import _StagingPool
    from ompi_tpu.mca.btl.sm import SmBtl
    from ompi_tpu.mca.btl.tcp import TcpBtl
    from ompi_tpu.mca.coll.tuned import TunedModule
    from ompi_tpu.mca.pml.ob1 import Ob1Pml
    from ompi_tpu.runtime import profile

    assert profile.enabled is False              # default off
    assert profile._profiler is None             # no sampler object
    assert not [t for t in threading.enumerate()
                if t.name == "otpu-prof"], "profiler thread exists"
    # start() without an interval stays off
    class _Rte:
        my_world_rank = 0

    assert profile.start(_Rte()) is False
    assert profile._profiler is None
    # disabled stage calls record NOTHING (no mark objects, no table
    # walk — a bogus name doesn't even raise)
    profile.stage_span("definitely.not.a.stage", 12345)
    profile.stage_mark("definitely.not.a.stage")
    assert profile.stage_snapshot() == {}
    assert profile.profiler_stats() is None
    # the instrumented datapath stays unwrapped plain functions
    for fn in (TcpBtl.send, TcpBtl._flush_locked, TcpBtl._on_bytes,
               SmBtl.send, SmBtl.progress, Ob1Pml.isend,
               Ob1Pml._recv_frag, Ob1Pml._recv_data_frag,
               TunedModule.allreduce, Convertor.pack_borrow,
               _StagingPool.acquire):
        assert not hasattr(fn, "__wrapped__"), fn


_PROFILE_PIN_SCRIPT = textwrap.dedent("""
    import json, os
    from ompi_tpu.rte.coord import CoordServer

    srv = CoordServer(1)
    os.environ["OTPU_COORD"] = f"{srv.addr[0]}:{srv.addr[1]}"
    os.environ["OTPU_RANK"] = "0"
    os.environ["OTPU_NPROCS"] = "1"

    import numpy as np, ompi_tpu
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import profile

    w = ompi_tpu.init()
    x = np.ones(1024, np.float32)               # 4KB payload
    buf = np.empty_like(x)

    # self send/recv crosses the instrumented pml datapath
    # (pack -> deliver -> complete) on a 1-rank world, where an
    # allreduce would shortcut past pml/btl entirely
    stages_var = registry.lookup("otpu_profile_stages")
    w.send(x, dest=0, tag=7)
    w.recv(buf, source=0, tag=7)
    idle = sum(v["n"] for v in profile.stage_stats().values())
    stages_var.set(True)
    w.send(x, dest=0, tag=7)
    w.recv(buf, source=0, tag=7)
    recorded = sum(v["n"] for v in profile.stage_stats().values())
    stages_var.set(False)
    print("PROFPIN " + json.dumps([idle, recorded]))
    ompi_tpu.finalize()
    srv.close()
""")


def test_profile_enabled_stage_clocks_record(tmp_path):
    """The enabled-stage-clock pin: armed, a 4KB self send/recv pays a
    few perf_counter_ns pairs + locked histogram folds per message, so
    the clocks must have recorded; disarmed, the same message must
    have recorded nothing."""
    script = tmp_path / "prof_pin.py"
    script.write_text(_PROFILE_PIN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines() if "PROFPIN" in ln)
    idle, recorded = json.loads(line.split("PROFPIN ", 1)[1])
    assert idle == 0, f"{idle} stage records while disarmed"
    assert recorded >= 1, "stage clocks never recorded while armed"
