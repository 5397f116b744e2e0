"""otpu-trace — always-on span tracing with per-rank ring buffers.

The missing *timeline* layer of the observability stack: SPC counts
(`runtime/spc.py`), monitoring sums per peer (`runtime/monitoring.py`),
PERUSE sees queue internals (`runtime/peruse.py`) — none of them record
WHEN a collective started and ended on each rank, so collective skew,
straggler ranks, and FT detection latency were invisible.  This module
records spans (name, category, t_start/t_end ns, args) and instant
events into a fixed-size per-rank ring buffer, plus log2-size-binned
latency histograms per collective exported as MPI_T pvars.

Hot-path discipline is peruse.py's: every instrumentation site is
guarded by the single module flag ``enabled`` — the disabled cost is one
attribute load + branch.  The enabled record path is lock-light: slot
allocation is one ``itertools.count`` bump (atomic in CPython), the ring
overwrites oldest entries, and only the histogram update takes a lock
(it is exact, the way SPC's relaxed counters are not).

At finalize each rank exports a Chrome trace-event JSON file
(``otpu_trace_dir`` cvar) and publishes the payload into the
CoordServer KV space so the launcher (``tools/tpurun.py``) can gather
every rank's timeline, align clocks with the mpisync offset estimator,
and emit one merged timeline plus a skew report.

**Causal flow keys (otpu-crit).**  Per-rank spans say what each rank
did; they cannot say which rank's message a recv waited on.  The flow
layer stamps every pml message span with a compact key —
``cid.src.dst.seq``, the (comm, sender, receiver, per-peer sequence)
tuple that ALREADY rides every btl match header — and every traced
collective span with ``(cid, cseq)``, a per-communicator collective
sequence every member rank counts identically (MPI requires identical
collective order per comm, so rank A's Nth collective on a cid IS rank
B's Nth).  Send completion and recv delivery additionally emit Chrome
flow events (``ph:"s"``/``"f"`` sharing an ``id``), so a merged
timeline renders real cross-rank message arrows and
``tools/otpu_analyze.py`` can assemble the cross-rank activity graph
(program-order edges, message edges, collective barrier edges) behind
``--critical-path``.  Guarded by its own module bool ``flow_enabled``
(`otpu_trace_flow`): flow-off runs pay nothing beyond the existing
``enabled`` checks.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import re
import threading
import time
from typing import Any, Optional

from ompi_tpu.base.var import PvarClass, VarType, registry

#: THE fast-path guard (peruse._active discipline): instrumentation
#: sites read this module attribute and branch — nothing else happens
#: while tracing is disabled.
enabled = False

#: the flow layer's own guard: true only while ``enabled`` AND the
#: ``otpu_trace_flow`` cvar is set.  Flow stamping sites (pml span
#: keys, flow_start/flow_finish, the coll-wrapper cseq) read this and
#: branch — a flow-disabled run records exactly what it did before
#: otpu-crit existed.
flow_enabled = False

#: the request layer's guard (otpu-req): true only while ``enabled``
#: AND the ``otpu_trace_requests`` cvar is set.  Serving call sites
#: (router stage stamps, worker prefill/kv/decode spans, the kv-slab
#: per-sequence flow hops) read this and branch — a requests-disabled
#: run records exactly what it did before otpu-req existed.
requests_enabled = False

#: Declared span categories (the registry ``otpu_info --trace``
#: enumerates; every ``trace.span``/``instant`` call site uses one).
CATEGORIES = {
    "boot": "instance boot path (coord connect, modex fence)",
    "build": "a device program's build, a span a phase (build.trace, "
             "build.lower, build.backend: a compile on a cache miss, a "
             "load on a hit), from JAX's own compile events",
    "btl": "transport-layer wire operations (sendmsg, ring push)",
    "chaos": "injected-fault instants (ft/chaos)",
    "coll": "collective invocations (c_coll interposition)",
    "ft": "failure detection/propagation/agreement + elastic recovery",
    "io": "MPI-IO (ompio) operations",
    "osc": "one-sided epochs (fence/lock/PSCW/flush)",
    "part": "partitioned communication (Pready/Parrived)",
    "pml": "point-to-point send/recv completion spans",
    "serving": "continuous-batching serving ticks",
    "serve_req": "per-request serving stage spans (otpu-req: queue/"
                 "dispatch/prefill/kv/decode/stream, args carry the "
                 "rid — otpu_analyze --requests consumes them)",
    "staging": "accelerator staging-pool checkouts",
    "step": "application/training step windows (critical-path unit)",
    "flow": "Chrome flow events binding send completion to recv "
            "delivery (ph s/f; otpu-crit message arrows)",
}

#: Declared flow-key categories: the closed vocabulary ``flow_start``/
#: ``flow_finish`` accept (otpu-lint's observability pass checks
#: literal call sites against this table, the STAGES discipline).  The
#: key format is part of the contract — otpu_analyze parses it.
FLOW_CATEGORIES = {
    "pml_msg": "one point-to-point message: send completion -> recv "
               "delivery, id 'cid.src.dst.seq' (world ranks; the "
               "per-(cid,src,dst) pml sequence that rides every btl "
               "match header)",
    "coll_round": "one collective round: every member rank's span "
                  "carries the same (cid, cseq) key in its args; the "
                  "analyzer builds last-arrival->all-release barrier "
                  "edges from it",
    "serve_req": "one serving-request hop: id 'rid.hop' where hop "
                 "numbers the causal chain router dispatch (0) -> "
                 "prefill shard -> KV slab Pready/Parrived (1) -> "
                 "decode/token stream (2) -> router completion; a "
                 "merged timeline renders one arrow chain per request "
                 "across router and worker ranks",
}

_ring: Optional[list] = None
_ring_n = 0
_slot = itertools.count()

#: per-communicator collective sequence counters (cid -> count); every
#: rank assigns cseq at record time in program order, so the counters
#: agree across ranks without any wire traffic
_coll_seq: dict = {}

#: wall/monotonic anchor pair: spans carry perf_counter_ns timestamps
#: (monotonic, ns resolution); export maps them onto the wall clock via
#: this pair so cross-rank merge has a common (pre-offset) timebase.
_anchor_wall_ns = time.time_ns()
_anchor_mono_ns = time.perf_counter_ns()

# histogram state: (coll, log2 size bin) -> [count, sum_ns, min_ns,
# max_ns, count_pvar, sum_pvar, {log2 dur bin: count}]; exact under
# _hist_lock (enabled path only).  The trailing dict is the log2
# LATENCY sub-histogram percentile estimation interpolates over.
_hist: dict = {}
_hist_lock = threading.Lock()

_events_pvar = None
_KV_KEY = "otpu_trace"
_DEFAULT_DIR = "otpu-trace"


def _sync_flow() -> None:
    # defensive lookup: the flow var's own registration may fire this
    # hook (env/file value applied) before the module global binds
    global flow_enabled
    var = globals().get("_flow_var")
    flow_enabled = enabled and (var is None or bool(var.value))


def _sync_requests() -> None:
    # same defensive lookup as _sync_flow, same reason — but note the
    # inverted default: flow rides enabled tracing unless opted OUT,
    # the request layer stays off unless opted IN
    global requests_enabled
    var = globals().get("_requests_var")
    requests_enabled = enabled and var is not None and bool(var.value)


def _set_enabled(value: bool) -> None:
    global enabled, _ring, _ring_n
    if value:
        want = max(1024, int(_buf_var.value or 65536))
        if _ring is None or want != _ring_n:
            # honor a buffer_events change across a disable/re-enable
            # cycle; the resize starts a fresh (empty) ring
            _ring_n = want
            _ring = [None] * want
    enabled = bool(value)
    _sync_flow()
    _sync_requests()


# buffer/dir/flow register first: registering the enable var applies
# its env/file value immediately, and the on_set hook sizes the ring
_dir_var = registry.register(
    "trace", None, "dir", vtype=VarType.STRING, default="",
    help="Directory for per-rank Chrome trace JSON written at finalize "
         f"(empty: '{_DEFAULT_DIR}' when tracing is enabled)")
_buf_var = registry.register(
    "trace", None, "buffer_events", vtype=VarType.INT, default=65536,
    help="Ring buffer capacity in events; the ring overwrites oldest "
         "entries, so a trace always holds the run's tail — the "
         "overwritten count is surfaced in the export metadata and the "
         "otpu_analyze report header")
_flow_var = registry.register(
    "trace", None, "flow", vtype=VarType.BOOL, default=True,
    help="Stamp pml message spans with their cid.src.dst.seq flow key "
         "(emitted as Chrome flow-event arrows) and collective spans "
         "with a per-comm (cid, cseq) round key — the causal edges "
         "otpu_analyze --critical-path consumes.  Only meaningful "
         "while tracing is enabled; off pins the pre-otpu-crit "
         "record path",
    on_set=lambda _v: _sync_flow())
_requests_var = registry.register(
    "trace", None, "requests", vtype=VarType.BOOL, default=False,
    help="Thread every serving request through the trace as a "
         "request-scoped span/flow layer: per-stage 'serve_req' spans "
         "(queue/dispatch/prefill/kv/decode/stream, keyed by rid) and "
         "a 'rid.hop' flow-arrow chain router -> prefill -> decode -> "
         "router riding the KV slab's per-sequence Pready keys — what "
         "otpu_analyze --requests decomposes.  Default off: the "
         "serving hot path pays nothing until a request-granular "
         "question is asked",
    on_set=lambda _v: _sync_requests())
_enable_var = registry.register(
    "trace", None, "enable", vtype=VarType.BOOL, default=False,
    help="Record span/instant events (pml, coll host+device, osc epochs, "
         "MPI-IO, FT) into the per-rank trace ring buffer and export "
         "Chrome trace JSON at finalize; disabled cost is one flag check",
    on_set=_set_enabled)


def init() -> None:
    """Register the tracer's own pvars (called from runtime init; safe
    to call repeatedly)."""
    global _events_pvar
    _events_pvar = registry.register_pvar(
        "trace", None, "events_recorded", pclass=PvarClass.COUNTER,
        help="Total trace events recorded (ring may have overwritten "
             "the oldest: capacity is otpu_trace_buffer_events)")
    _events_pvar.on_read = \
        lambda: _events_pvar.set(float(recorded_count()))


def recorded_count() -> int:
    """Total events ever recorded: the highest slot index still in the
    ring, +1.  Slot allocation is the one atomic counter (itertools
    .count), so this needs no second — racy — accumulator; overwritten
    events can only have LOWER indices than the survivors."""
    if _ring is None:
        return 0
    return max((e[-1] for e in _ring if e is not None), default=-1) + 1


def now() -> int:
    """Span start timestamp (perf_counter_ns)."""
    return time.perf_counter_ns()


def span(name: str, cat: str, t_start: int, t_end: Optional[int] = None,
         args: Optional[dict] = None) -> None:
    """Record one complete span.  Callers capture ``t_start = trace.now()``
    inside their own ``if trace.enabled`` guard."""
    if not enabled:
        return
    if t_end is None:
        t_end = time.perf_counter_ns()
    i = next(_slot)
    _ring[i % _ring_n] = ("X", name, cat, t_start, t_end - t_start,
                          threading.get_ident(), args, i)


def instant(name: str, cat: str, args: Optional[dict] = None) -> None:
    """Record one instant event (FT detection, propagation, delivery)."""
    if not enabled:
        return
    i = next(_slot)
    _ring[i % _ring_n] = ("i", name, cat, time.perf_counter_ns(), 0,
                          threading.get_ident(), args, i)


# -- causal flow events (otpu-crit) --------------------------------------

def _flow_id(fid) -> str:
    """Normalize a flow key to the Chrome id string: tuple keys (what
    @hot_path call sites pass — string building is banned there) render
    dot-joined, matching the documented ``cid.src.dst.seq`` format."""
    return fid if isinstance(fid, str) else ".".join(map(str, fid))


def flow_start(fcat: str, fid, t_ns: Optional[int] = None) -> None:
    """Record the producing half of one flow edge (Chrome ``ph:"s"``).

    ``fcat`` must be a :data:`FLOW_CATEGORIES` key (otpu-lint-enforced
    at literal call sites); ``fid`` is the category's documented key —
    a string or a tuple rendered dot-joined.  ``t_ns`` anchors the
    arrow inside the emitting span — callers pass the span's own end
    timestamp so viewers bind the flow to that slice."""
    if not flow_enabled:
        return
    from ompi_tpu.runtime import spc

    spc.record("flow_starts")
    i = next(_slot)
    _ring[i % _ring_n] = ("s", fcat, "flow",
                         t_ns if t_ns is not None
                         else time.perf_counter_ns(), 0,
                         threading.get_ident(), {"id": _flow_id(fid)}, i)


def flow_finish(fcat: str, fid, t_ns: Optional[int] = None) -> None:
    """Record the consuming half of one flow edge (Chrome ``ph:"f"``,
    bound to the enclosing slice via ``bp:"e"``)."""
    if not flow_enabled:
        return
    from ompi_tpu.runtime import spc

    spc.record("flow_finishes")
    i = next(_slot)
    _ring[i % _ring_n] = ("f", fcat, "flow",
                         t_ns if t_ns is not None
                         else time.perf_counter_ns(), 0,
                         threading.get_ident(), {"id": _flow_id(fid)}, i)


def next_coll_seq(cid: int) -> int:
    """Allocate this rank's next collective sequence number on ``cid``
    (the coll_round flow key's second half).  Program order per comm is
    identical on every member rank by MPI semantics, so the counters
    agree with zero wire traffic; assignment happens at record time, so
    ring overwrite can never desynchronise surviving spans."""
    c = _coll_seq.get(cid)
    if c is None:
        c = _coll_seq.setdefault(cid, itertools.count())
    return next(c)


# -- log2-size-binned latency histograms --------------------------------

def _bin_label(b: int) -> str:
    """Human label of log2 bin ``b`` (its lower bound): 0, 1b..512b,
    1k..512k, 1m.."""
    if b == 0:
        return "0"
    lo = 1 << (b - 1)
    if lo < (1 << 10):
        return f"{lo}b"
    if lo < (1 << 20):
        return f"{lo >> 10}k"
    if lo < (1 << 30):
        return f"{lo >> 20}m"
    return f"{lo >> 30}g"


def hist_record(coll: str, nbytes: int, dur_ns: int) -> None:
    """Fold one collective invocation into its (coll, log2 size) bin and
    the bin's MPI_T pvars (lazily registered on first hit so the pvar
    namespace only carries bins the run actually touched)."""
    b = int(nbytes).bit_length()
    key = (coll, b)
    with _hist_lock:
        cell = _hist.get(key)
        if cell is None:
            label = _bin_label(b)
            cnt = registry.register_pvar(
                "trace", "hist", f"{coll}_{label}_count",
                pclass=PvarClass.COUNTER,
                help=f"{coll} invocations in the [{label}, next-bin) "
                     "payload size bin")
            tot = registry.register_pvar(
                "trace", "hist", f"{coll}_{label}_sum_us",
                pclass=PvarClass.AGGREGATE,
                help=f"Summed {coll} latency (us) in the [{label}, "
                     "next-bin) payload size bin")
            cell = _hist[key] = [0, 0, dur_ns, dur_ns, cnt, tot, {}]
            for q, qname in ((0.5, "p50"), (0.99, "p99")):
                pv = registry.register_pvar(
                    "trace", "hist", f"{coll}_{label}_{qname}_us",
                    pclass=PvarClass.LEVEL,
                    help=f"{qname} {coll} latency (us, interpolated from "
                         f"the log2 latency bins) in the [{label}, "
                         "next-bin) payload size bin")
                # pre-read hook: percentiles are derived, not accumulated
                pv.on_read = (lambda pv=pv, key=key, q=q:
                              pv.set(_key_percentile_us(key, q)))
        cell[0] += 1
        cell[1] += dur_ns
        cell[2] = min(cell[2], dur_ns)
        cell[3] = max(cell[3], dur_ns)
        cell[4].add_relaxed(1)
        cell[5].add_relaxed(dur_ns / 1000.0)
        db = int(dur_ns).bit_length()
        cell[6][db] = cell[6].get(db, 0) + 1


def histograms() -> dict:
    """{(coll, bin_label): (count, sum_us, min_us, max_us)} snapshot."""
    with _hist_lock:
        return {
            (coll, _bin_label(b)): (c[0], c[1] / 1000.0, c[2] / 1000.0,
                                    c[3] / 1000.0)
            for (coll, b), c in _hist.items()
        }


def _interp_percentile_ns(dur_bins: dict, q: float, lo_clamp: int,
                          hi_clamp: int) -> float:
    """Estimate the q-quantile (ns) from a {log2 bin: count} latency
    histogram: find the bin holding the q*N-th sample and interpolate
    linearly inside it (bin b covers [2^(b-1), 2^b)), clamped to the
    exact observed [min, max] so single-bin cells don't over-report."""
    total = sum(dur_bins.values())
    if total == 0:
        return 0.0
    target = q * total
    cum = 0.0
    est = float(hi_clamp)
    for b in sorted(dur_bins):
        cnt = dur_bins[b]
        if cum + cnt >= target:
            lo = 0 if b == 0 else (1 << (b - 1))
            hi = 1 if b == 0 else (1 << b)
            frac = (target - cum) / cnt
            est = lo + frac * (hi - lo)
            break
        cum += cnt
    return float(max(lo_clamp, min(hi_clamp, est)))


def _key_percentile_us(key, q: float) -> float:
    """q-quantile (us) of ONE (coll, size-bin) cell (pvar read hook)."""
    with _hist_lock:
        cell = _hist.get(key)
        if cell is None:
            return 0.0
        return _interp_percentile_ns(cell[6], q, cell[2], cell[3]) / 1000.0


def hist_snapshot() -> dict:
    """Deep-copied histogram state for delta consumers (the telemetry
    sampler): ``{(coll, size_bin): (count, sum_ns, min_ns, max_ns,
    {log2 dur bin: count})}``.  Pure read — the live populations are
    NEVER reset or otherwise disturbed, so a sampler can snapshot at
    its own cadence while percentile pvars, ``hist_percentile`` and the
    finalize export keep seeing the full-run populations."""
    with _hist_lock:
        return {k: (c[0], c[1], c[2], c[3], dict(c[6]))
                for k, c in _hist.items()}


def hist_delta_stats(prev: dict, cur: dict) -> dict:
    """Per-collective interval statistics between two
    :func:`hist_snapshot` results: ``{coll: {"n": invocations,
    "sum_us": total latency, "p50_us": ..., "p99_us": ...}}`` computed
    from the BIN-COUNT DELTAS (size bins merged per collective), so the
    percentiles describe only the interval's population.  Collectives
    with no new invocations are omitted — the samples stay compact.
    ``bytes`` is a payload-volume estimate (count x size-bin lower
    bound, exact to within one log2 bin) — the live-rate signal for
    traffic that never touches the pml SPC counters (sm collectives)."""
    merged: dict = {}   # coll -> [dn, dsum_ns, {dur bin: dcount}, bytes]
    clamps: dict = {}        # coll -> [lo_ns, hi_ns] (from cur cells)
    for key, cell in cur.items():
        coll = key[0]
        old = prev.get(key)
        dn = cell[0] - (old[0] if old else 0)
        if dn <= 0:
            continue
        dsum = cell[1] - (old[1] if old else 0)
        acc = merged.setdefault(coll, [0, 0, {}, 0])
        acc[0] += dn
        acc[1] += dsum
        b = key[1]
        acc[3] += dn * (0 if b == 0 else (1 << (b - 1)))
        old_bins = old[4] if old else {}
        for db, cnt in cell[4].items():
            d = cnt - old_bins.get(db, 0)
            if d > 0:
                acc[2][db] = acc[2].get(db, 0) + d
        cl = clamps.setdefault(coll, [cell[2], cell[3]])
        cl[0] = min(cl[0], cell[2])
        cl[1] = max(cl[1], cell[3])
    out = {}
    for coll, (dn, dsum, dbins, dbytes) in merged.items():
        lo, hi = clamps[coll]
        out[coll] = {
            "n": dn,
            "bytes": dbytes,
            "sum_us": round(dsum / 1000.0, 1),
            "p50_us": round(
                _interp_percentile_ns(dbins, 0.5, lo, hi) / 1000.0, 1),
            "p99_us": round(
                _interp_percentile_ns(dbins, 0.99, lo, hi) / 1000.0, 1),
        }
    return out


def hist_reset(coll: str) -> None:
    """Drop every histogram cell of ``coll`` so the next records start
    a fresh population — measurement harnesses (the serving driver) use
    this to keep per-run percentiles from merging with an earlier run's
    samples in the same process.  The cells' pvars stay registered
    (counters remain cumulative, like every SPC pvar); the percentile
    pvars re-bind to the new cells on the next record."""
    with _hist_lock:
        for key in [k for k in _hist if k[0] == coll]:
            del _hist[key]


def hist_percentile(coll: str, q: float,
                    nbytes: Optional[int] = None) -> float:
    """Estimated q-quantile latency in MICROSECONDS of ``coll``'s
    recorded invocations — interpolated from the log2-duration bins the
    histogram keeps per cell (exact to within one log2 bin; the serving
    driver's p50/p99 report and ``otpu_info --pvars`` read this).

    ``nbytes`` restricts the estimate to that payload's size bin;
    without it the duration bins of every size bin are merged."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    if nbytes is not None:
        return _key_percentile_us((coll, int(nbytes).bit_length()), q)
    with _hist_lock:
        merged: dict = {}
        lo_clamp, hi_clamp, any_cell = None, 0, False
        for (c, _b), cell in _hist.items():
            if c != coll:
                continue
            any_cell = True
            lo_clamp = cell[2] if lo_clamp is None else min(lo_clamp,
                                                            cell[2])
            hi_clamp = max(hi_clamp, cell[3])
            for db, cnt in cell[6].items():
                merged[db] = merged.get(db, 0) + cnt
        if not any_cell:
            return 0.0
        return _interp_percentile_ns(merged, q, lo_clamp, hi_clamp) / 1000.0


# -- per-comm coll table interposition ----------------------------------

#: collectives whose first argument carries the payload (superset of
#: monitoring's set: the device *_array entry points are sized too)
_SIZED_COLLS = {
    "bcast", "allreduce", "reduce", "allgather", "allgatherv", "alltoall",
    "reduce_scatter", "reduce_scatter_block", "gather", "gatherv",
    "scatter", "scan", "exscan",
    "ibcast", "iallreduce", "ireduce", "iallgather", "ialltoall",
    "igather", "iscatter", "ireduce_scatter", "iscan", "iexscan",
    "allreduce_array", "bcast_array", "allgather_array",
    "allgatherv_array", "reduce_scatter_array", "alltoall_array",
    "alltoallv_array", "ppermute_array", "psum_scatter_array",
    "reduce_array", "gather_array", "scatter_array", "scan_array",
    "exscan_array",
}


def _no_profiler() -> bool:
    return False


#: The device path's second switch: an open JAX profiler session.  The
#: device slots write one ``otpu.coll.<slot>`` span per call into the
#: profiler's own trace (``jax.profiler.trace`` / ``start_trace``), on
#: the clock the device timeline is on, and nothing when no session is
#: open.  Both stay jax-free until a device module — which imports jax
#: anyway — calls :func:`bind_profiler`: the launcher imports this
#: module with the base layer alone.
profiler_on = _no_profiler      # TraceAnnotation.is_enabled once bound
profiler_span = None            # jax.profiler.TraceAnnotation once bound


def bind_profiler() -> None:
    global profiler_on, profiler_span
    if profiler_span is None:
        from jax.profiler import TraceAnnotation

        profiler_span = TraceAnnotation
        profiler_on = TraceAnnotation.is_enabled


# -- a device program's own build -----------------------------------------

#: The device path's jitted functions that are not named ``otpu_*``: the
#: build record tells the program's own programs from a caller's by the
#: name JAX's compile events carry, and a reader of a profiler's trace
#: knows these by the names they have.  ``tests/test_build_record.py``
#: walks the device path's sources and holds every ``jax.jit`` to the
#: rule (:func:`own_program`).
OWN_PROGRAMS = (
    "reduce_stack", "combine2",                     # ops/pallas_reduce
    "transpose_blocks",                             # ops/pallas_ddt
    "gmm", "tgmm",                                  # ops/grouped_matmul
    "row_scatter_add",                              # ops/row_scatter
    "flash_causal_forward", "attn_block_backward",  # ops/flash_attention
    "index_select", "index_loss",                   # ops/sparse_attention
    "rule_forward", "rule_backward",                # ops/gated_delta
    "scan_forward", "scan_backward",                # ops/ssd_scan
    "conv_forward", "conv_backward",                # ops/causal_conv
    "encode_int8", "decode_int8", "dequant_accumulate",  # ops/pallas_quant
)

#: JAX's events of one build, in order: (phase, the counter of the
#: program's own).  Each comes as a scalar at entry and a duration at
#: exit, with the function's name (``f`` while tracing, ``jit(f)`` after).
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("trace", "device_program_trace_us"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "device_program_lower_us"),
    "/jax/core/compile/backend_compile_duration":
        ("backend", "device_program_backend_us"),
}
#: the persistent cache's events carry no name: they belong to the
#: event open on their thread
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_builds = threading.local()     # .open: this thread's open events,
                                # outermost first; .suspended: a depth
_bind_lock = threading.Lock()
_spc_record = None              # spc.record once bound


def own_program(fun_name: str) -> bool:
    """Whether a compile event's ``fun_name`` names one of the device
    path's programs: ``otpu_*`` or one of :data:`OWN_PROGRAMS`, bare (the
    trace event) or as ``jit(<name>)`` (the other two)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    return fun_name.startswith("otpu_") or fun_name in OWN_PROGRAMS


def bind_builds() -> None:
    """Register the build record's listeners with ``jax.monitoring``,
    once a process; bound where :func:`bind_profiler` is, so the base
    layer stays jax-free.  JAX calls them only while it builds
    something: a cached call reaches none."""
    global _spc_record
    if _spc_record is not None:
        return
    with _bind_lock:
        if _spc_record is not None:
            return
        import jax.monitoring as mon

        from ompi_tpu.runtime import spc

        _spc_record = spc.record
        mon.register_scalar_listener(_build_enter)
        mon.register_event_listener(_build_event)
        mon.register_event_duration_secs_listener(_build_exit)


@contextlib.contextmanager
def builds_suspended():
    """``with trace.builds_suspended():`` books nothing of what this
    thread builds inside: for a program compiled again only to be read
    (``step.scopes()``, ``step.memory()``)."""
    _builds.suspended = getattr(_builds, "suspended", 0) + 1
    try:
        yield
    finally:
        _builds.suspended -= 1


def _build_enter(event, _value=None, **kw) -> None:
    """Scalar listener: a phase opens on this thread."""
    try:
        if event in _BUILD_PHASES and not getattr(_builds, "suspended", 0):
            # [event, name, cache requests, hits, load us]
            _builds.__dict__.setdefault("open", []).append(
                [event, str(kw.get("fun_name", "")), 0, 0, 0.0])
    except Exception:       # never into JAX's compile path
        pass


def _build_event(event, **_kw) -> None:
    """Plain-event listener: a request to the persistent cache, or a hit,
    by the build open on this thread."""
    try:
        if event == _CACHE_REQUEST or event == _CACHE_HIT:
            stack = getattr(_builds, "open", None)
            if stack:
                stack[-1][2 if event == _CACHE_REQUEST else 3] += 1
    except Exception:
        pass


def _build_exit(event, secs=0.0, **_kw) -> None:
    """Duration listener: a phase closes.  Only the outermost event of a
    thread is booked: a ``jax.jit`` traced inside another's trace, or an
    eager operation compiled there, lies inside the outer phase's seconds
    already and hands it its cache requests."""
    try:
        stack = getattr(_builds, "open", None)
        if not stack:
            return
        if event == _CACHE_LOAD:
            stack[-1][4] += secs * 1e6
            return
        if event not in _BUILD_PHASES:
            return
        at = len(stack) - 1
        while at >= 0 and stack[at][0] != event:
            at -= 1
        if at < 0:
            return              # an exit whose entry was never seen
        entry = stack[at]
        del stack[at:]
        if stack:
            for i in (2, 3, 4):
                stack[0][i] += entry[i]
            return
        _book_build(entry, secs)
    except Exception:
        pass


def _book_build(entry: list, secs: float) -> None:
    """One outermost phase into the counters and, while the ring is on,
    one ``build`` span on the ring's clock: JAX's events come on
    ``time.time()``, so the span ends now and starts ``secs`` earlier."""
    event, name, requests, hits, load_us = entry
    phase, counter = _BUILD_PHASES[event]
    own = own_program(name)
    if own:
        _spc_record(counter, secs * 1e6)
        if phase == "backend":
            _spc_record("device_programs_compiled")
        if requests:
            _spc_record("device_program_cache_requests", requests)
        if hits:
            _spc_record("device_program_cache_hits", hits)
    else:
        _spc_record("device_other_build_us", secs * 1e6)
    if enabled:
        t_end = time.perf_counter_ns()
        args = {"program": name, "own": own}
        if phase == "backend" or requests:
            args["cache"] = "hit" if hits and hits >= requests else "miss"
            args["load_us"] = load_us
        span("build." + phase, "build", t_end - int(secs * 1e9), t_end,
             args=args)


def wrap_coll_table(comm) -> None:
    """coll/trace interposition: wrap every selected c_coll slot with a
    span + histogram recorder.  Installed unconditionally at comm_select
    (tracing can be switched on mid-run through MPI_T); the wrapper's
    disabled path is one flag check, verified by test_perf_guard — and,
    on the device slots (``*_array``), one ``profiler_on()`` before it:
    with a profiler session open the call runs inside
    ``TraceAnnotation("otpu.coll.<slot>")``, which is never constructed
    otherwise."""

    def make(name, fn):
        def ring(comm_arg, *args, **kw):
            if not enabled:
                return fn(comm_arg, *args, **kw)
            # .nbytes is an attribute on both numpy and jax arrays — no
            # np.asarray here, which would pull a device buffer to host
            nbytes = 0
            if name in _SIZED_COLLS and args:
                nbytes = getattr(args[0], "nbytes", 0) or 0
            # coll_round flow key: cseq allocated BEFORE the collective
            # runs, in program order — every member rank's span for this
            # round carries the same (cid, cseq)
            cseq = next_coll_seq(comm_arg.cid) if flow_enabled else None
            t0 = time.perf_counter_ns()
            try:
                return fn(comm_arg, *args, **kw)
            finally:
                t1 = time.perf_counter_ns()
                eargs = {"nbytes": int(nbytes), "cid": comm_arg.cid}
                if cseq is not None:
                    eargs["cseq"] = cseq
                span(name, "coll", t0, t1, args=eargs)
                hist_record(name, int(nbytes), t1 - t0)

        if not name.endswith("_array"):
            traced = ring
        else:
            pname = "otpu.coll." + name     # built once per slot

            def traced(comm_arg, *args, **kw):
                if profiler_on():
                    with profiler_span(pname):
                        return ring(comm_arg, *args, **kw)
                if not enabled:
                    return fn(comm_arg, *args, **kw)
                return ring(comm_arg, *args, **kw)

        # carry the inner slot's marker attributes (__sync_wrapped__,
        # __monitored__, ...) — interposition layers and tests probe the
        # outermost callable for them
        traced.__dict__.update(getattr(fn, "__dict__", {}))
        traced.__traced__ = True
        traced.__wrapped__ = fn
        traced.__self__ = getattr(fn, "__self__", None)
        return traced

    for name, fn in list(comm.c_coll.items()):
        if not getattr(fn, "__traced__", False):
            comm.c_coll[name] = make(name, fn)


# -- export --------------------------------------------------------------

def _wall_us(t_ns: int) -> float:
    return (_anchor_wall_ns + (t_ns - _anchor_mono_ns)) / 1000.0


def chrome_events() -> list:
    """Ring contents as Chrome trace-event dicts (ts/dur in wall-clock
    microseconds), oldest first."""
    if _ring is None:
        return []
    events = [e for e in _ring if e is not None]
    events.sort(key=lambda e: e[3])
    out = []
    for ph, name, cat, t0, dur, tid, eargs, _slot_i in events:
        ev = {"ph": ph, "name": name, "cat": cat,
              "ts": _wall_us(t0), "tid": tid}
        if ph == "X":
            ev["dur"] = dur / 1000.0
        if ph in ("s", "f"):
            # flow events: the id is a top-level field in the Chrome
            # schema; "f" binds to its enclosing slice (bp:"e") so the
            # arrow lands on the recv span, not the next event
            eargs = dict(eargs or {})
            ev["id"] = eargs.pop("id", "")
            if ph == "f":
                ev["bp"] = "e"
        if eargs:
            ev["args"] = eargs
        out.append(ev)
    return out


def chrome_payload(rank: int, clock_offset_us: float = 0.0,
                   extra_meta: Optional[dict] = None) -> dict:
    """Full per-rank Chrome trace JSON object (events + metadata)."""
    import socket

    recorded = recorded_count()
    events = chrome_events()
    for ev in events:
        ev["pid"] = rank
    meta = {
        "rank": rank,
        "host": socket.gethostname(),
        "pid_os": os.getpid(),
        "clock_offset_us": clock_offset_us,
        "events_recorded": recorded,
        "events_overwritten": max(0, int(recorded) - len(events)),
        "trace_dir": str(_dir_var.value or _DEFAULT_DIR),
    }
    if extra_meta:
        meta.update(extra_meta)
    return {"traceEvents": events, "metadata": meta}


def _estimate_coord_offset(client) -> float:
    """This rank's wall clock MINUS the coord server's clock, in us
    (the sign convention ``merge_timelines``/``skew_report`` consume:
    ``ts - offset`` lands every rank on the coord timebase), via the
    mpisync min-RTT estimator.  ``estimate_offset`` reports the peer's
    clock minus ours, hence the negation."""
    from ompi_tpu.tools.mpisync import estimate_offset

    off_s, _rtt = estimate_offset(client.server_time, iters=5)
    return -off_s * 1e6


def finalize_export(rte) -> None:
    """Called from runtime finalize (while the coord client is still
    alive): write this rank's Chrome trace JSON and publish the payload
    into the CoordServer KV space for the launcher-side merge."""
    if not enabled or _ring is None:
        return
    rank = int(getattr(rte, "my_world_rank", 0) or 0)
    client = getattr(rte, "client", None)
    offset_us = 0.0
    if client is not None:
        try:
            offset_us = _estimate_coord_offset(client)
        except Exception:
            offset_us = 0.0
    # otpu-prof rides in the payload metadata: the per-rank stage
    # breakdown reaches the launcher/analyzer over the same file + KV
    # gather the timeline already takes
    extra_meta = None
    try:
        from ompi_tpu.runtime import profile as _profile

        prof = _profile.export_payload()
        if prof is not None:
            extra_meta = {"profile": prof}
    except Exception:
        extra_meta = None
    payload = chrome_payload(rank, clock_offset_us=offset_us,
                             extra_meta=extra_meta)
    tdir = payload["metadata"]["trace_dir"]
    encoded = json.dumps(payload)   # one encode serves file AND publish
    try:
        os.makedirs(tdir, exist_ok=True)
        with open(os.path.join(tdir, f"trace_rank{rank}.json"), "w") as f:
            f.write(encoded)
    except OSError:
        pass   # unwritable dir must not break finalize
    if client is not None:
        try:
            client.put(rank, _KV_KEY, encoded)
        except Exception:
            pass   # coord gone: the per-rank file still exists


# -- launcher-side merge (used by tools/tpurun.py) -----------------------

def merge_timelines(payloads: list) -> list:
    """Merge per-rank Chrome payloads into one clock-aligned event list:
    each rank's timestamps are shifted by its measured offset to the
    coord clock, pid is the world rank."""
    merged = []
    for p in payloads:
        meta = p.get("metadata", {})
        off_us = float(meta.get("clock_offset_us", 0.0))
        rank = int(meta.get("rank", 0))
        for ev in p.get("traceEvents", []):
            e = dict(ev)
            e["ts"] = float(e["ts"]) - off_us
            e["pid"] = rank
            merged.append(e)
    merged.sort(key=lambda e: e["ts"])
    return merged


def _percentile(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


def skew_report(payloads: list) -> str:
    """Cross-rank skew analysis of the collective spans: per
    (collective, communicator) the arrival spread (start-time skew of
    matched rounds), the most-often-slowest rank, and p50/p99 latency
    by log2 size bin.

    Rounds are matched per (name, cid) by occurrence index FROM THE
    TAIL: the ring overwrites oldest events, so when ranks lost unequal
    prefixes only the newest min-count occurrences still line up across
    ranks.  Grouping by cid keeps a sub-communicator's collectives from
    being index-matched against another comm's rounds."""
    per_rank: dict = {}       # rank -> (name, cid) -> [(ts, dur, nbytes)]
    overwritten = 0
    for p in payloads:
        meta = p.get("metadata", {})
        rank = int(meta.get("rank", 0))
        off_us = float(meta.get("clock_offset_us", 0.0))
        overwritten += int(meta.get("events_overwritten", 0) or 0)
        by_key = per_rank.setdefault(rank, {})
        for ev in p.get("traceEvents", []):
            if ev.get("cat") != "coll" or ev.get("ph") != "X":
                continue
            eargs = ev.get("args") or {}
            key = (ev["name"], eargs.get("cid"))
            by_key.setdefault(key, []).append(
                (float(ev["ts"]) - off_us, float(ev.get("dur", 0.0)),
                 int(eargs.get("nbytes", 0))))
    ranks = sorted(per_rank)
    keys = sorted({k for d in per_rank.values() for k in d},
                  key=lambda k: (k[0], str(k[1])))
    lines = [f"otpu-trace skew report — {len(ranks)} ranks "
             f"({', '.join(str(r) for r in ranks)})"]
    if overwritten:
        lines.append(
            f"note: {overwritten} events overwritten across ranks (ring "
            "capacity otpu_trace_buffer_events); rounds are tail-aligned")
    lines += ["",
              "collective          cid  rounds  spread_mean_us  "
              "spread_max_us  slowest_rank"]
    bin_lat: dict = {}           # (name, bin_label) -> [dur...]
    for key in keys:
        name, cid = key
        seqs = {r: per_rank[r].get(key, []) for r in ranks}
        # rounds match across the ranks that HAVE spans for this key: a
        # rank with none (died early, ring-wrapped, or sat out the comm
        # — crash bundles produce all three) must not zero every other
        # rank's rounds and erase the survivors' skew
        members = [r for r in ranks if seqs[r]]
        rounds = min((len(seqs[r]) for r in members), default=0) \
            if len(members) >= 2 else 0
        # tail-align: the ring keeps the newest events on every rank
        tails = {r: seqs[r][len(seqs[r]) - rounds:] for r in members}
        spreads, slow_count = [], {}
        for k in range(rounds):
            starts = {r: tails[r][k][0] for r in members}
            durs = {r: tails[r][k][1] for r in members}
            spreads.append(max(starts.values()) - min(starts.values()))
            slowest = max(durs, key=durs.get)
            slow_count[slowest] = slow_count.get(slowest, 0) + 1
        for r in ranks:
            for _ts, dur, nbytes in tails.get(r, []) if rounds \
                    else seqs[r]:
                label = _bin_label(int(nbytes).bit_length())
                bin_lat.setdefault((name, label), []).append(dur)
        cid_s = "-" if cid is None else str(cid)
        if rounds:
            slowest_rank = max(slow_count, key=slow_count.get)
            absent = len(ranks) - len(members)
            lines.append(
                f"{name:<18}  {cid_s:>3}  {rounds:>6}"
                f"  {sum(spreads)/len(spreads):>14.1f}"
                f"  {max(spreads):>13.1f}  {slowest_rank:>12}"
                f"  ({slow_count[slowest_rank]}/{rounds} rounds"
                + (f"; {absent} rank(s) absent)" if absent else ")"))
        else:
            # unmatched across ranks (some rank never ran it): note only
            total = sum(len(s) for s in seqs.values())
            lines.append(f"{name:<18}  {cid_s:>3}  {0:>6}  "
                         f"{'-':>14}  {'-':>13}  {'-':>12}  "
                         f"({total} unmatched spans)")
    lines += ["", "latency by log2 payload-size bin:",
              "collective          bin      n     p50_us     p99_us"]
    for (name, label), durs in sorted(bin_lat.items()):
        durs.sort()
        lines.append(
            f"{name:<18}  {label:>5}  {len(durs):>5}  "
            f"{_percentile(durs, 0.50):>9.1f}  {_percentile(durs, 0.99):>9.1f}")
    return "\n".join(lines) + "\n"


# -- a train step's device time by the program's own scopes --------------

#: Every ``jax.named_scope`` the model train step (``parallel/train``,
#: ``model``, ``moe``) wraps a part of itself in, outermost first as
#: they nest.  JAX writes the open scopes into each HLO instruction's
#: ``metadata={op_name=...}`` and XLA keeps the root's on the fusions it
#: forms, so an op of a profiler's trace is joined to its scopes by the
#: instruction's name (:func:`scope_map`).  A scope new to the step goes
#: here too (``tests/test_train_scopes.py`` holds the sources to it);
#: the benchmark's ``harness/scopes.json`` repeats the tuple.
STEP_SCOPES = (
    "otpu_embed",           # the token embedding's gather (and scatter)
    "otpu_layers",          # the walk over the decoder layers: the scan,
                            # its carries, the residual adds
    "otpu_mla",             # a layer's latent attention sublayer
    "otpu_attention",       # a layer's OLMoE attention sublayer
    "otpu_attn_proj",       # inside either: norms, projections, RoPE,
                            # the head split; what is left is the kernels
    "otpu_dense_mlp",       # a dense layer's SwiGLU
    "otpu_moe",             # a sparse layer's expert block, whole
    "otpu_router",          # inside it: logits, scores, the choice
    "otpu_dispatch",        # sorting the slots, counting them, gathering
    "otpu_experts",         # the grouped matmuls
    "otpu_combine",         # weighting the slots' outputs, adding them up
    "otpu_shared_expert",   # the shared expert's SwiGLU
    "otpu_cast",            # a parameter leaf cast (or transposed) for a
                            # matmul, inside whichever part uses it
    "otpu_head",            # final norm, blocked cross-entropy
    "otpu_mtp",             # the next-next-token module, whole
    "otpu_loss",            # the auxiliary losses, the loss's sums
    "otpu_stats",           # what a step reports: sampled rows, gradient
                            # sums of squares, probes
    "otpu_grad_sync",       # the gradients' sum over the data axis
    "otpu_adamw",           # the update of every trained leaf
    "otpu_bias_update",     # the routers' balancing biases
    # behind this line: names the benchmark's harness/scopes.json does
    # not repeat (it is the leading part of this tuple); a metric file
    # that reads one lists it under its own ``vocabulary``
    "otpu_mamba",           # a layer's Mamba-2 mixer, whole
    "otpu_ssm_proj",        # inside it: the pre-norm, in_proj, out_proj
    "otpu_ssm_conv",        # the causal depthwise convolution and its silu
    "otpu_ssm_scan",        # softplus, the chunked state-space scan, D x
    "otpu_ssm_norm",        # the gate and the grouped norm
    "otpu_latent",          # inside otpu_moe: the latent's two projections
    "otpu_conv",            # a layer's gated short convolution, whole
    "otpu_conv_proj",       # inside it: the pre-norm, in_proj, out_proj
    "otpu_conv_gate",       # B * u, the taps, C * z
    "otpu_gdn",             # a layer's Gated DeltaNet operator, whole
    "otpu_gdn_proj",        # inside it: the pre-norm, W_qkvz, W_ba, W_out
    "otpu_gdn_conv",        # the causal depthwise convolution and its silu
    "otpu_gdn_rule",        # L2 norms, g, beta, the chunks, the recurrence
    "otpu_gdn_norm",        # the gated norm: RMSNorm a head, silu(z)
    "otpu_swa",             # a sliding-window layer's attention sublayer
                            # (a full layer's keeps otpu_attention)
    "otpu_dsa",             # a learned sparse attention sublayer, whole
                            # (in otpu_attention's place)
    "otpu_dsa_index",       # inside it: the indexer's projections, norm,
                            # RoPE and score blocks
    "otpu_dsa_select",      # the exact top-k and whatever builds the mask
    "otpu_dsa_loss",        # pbar, the KL, the indexer's backward
    "otpu_bd",              # a block-diffusion attention sublayer, whole
                            # (in otpu_attention's place)
    "otpu_bd_noise",        # the step's noise: levels, the masked copy,
                            # the two copies' ids side by side
    "otpu_bd_loss",         # inside otpu_head: the masked rows' weights
                            # and the weighted sum
    "otpu_loop_pass",       # a looped model's pass, whole: the layers'
                            # walk (otpu_layers inside) and the pass's norm
    "otpu_exit_gate",       # the exit gate's product, lambda, p, log p
    "otpu_exit_loss",       # the expected loss's and the entropy's own
                            # work, inside otpu_head and beside it
    "otpu_hc",              # the residual path around one sublayer of a
                            # model with several residual streams, whole
                            # (parallel/hyper.py; the streams' copies lie
                            # under otpu_embed, their sum under otpu_head)
    "otpu_hc_maps",         # inside it: the stream's norm, x' phi, the
                            # gates, the two sigmoids
    "otpu_hc_sinkhorn",     # the clamp, the exponential, the sweeps
    "otpu_hc_read",         # Hpre X: the sublayer's input
    "otpu_hc_write",        # Hres X + Hpost^T y: the stream behind it
)
#: the scopes whose ops are the optimiser's, whatever else their path says
UPDATE_SCOPES = ("otpu_adamw", "otpu_bias_update")
#: an instruction's pass, by its path: see :func:`scope_of_path`
PASSES = ("forward", "remat", "backward", "update")
#: instructions that run nothing, and so are no op of a trace
TRIVIAL_OPCODES = ("parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast")

_SCOPE_NAME_RE = re.compile(r"otpu_\w+")
_HLO_MODULE_RE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTRUCTION_RE = re.compile(
    r"^\s+(ROOT )?%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS_RE = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_HLO_OPERAND_RE = re.compile(r"(?<![=\w])%([\w.\-]+)")
#: what hands no scope on to a neighbour: it holds every scope's values
_NO_INHERITANCE = ("tuple", "get-tuple-element", "parameter", "while",
                   "call", "conditional")


def scope_of_path(path: str, vocabulary=STEP_SCOPES) -> tuple:
    """``(chain, pass, unknown)`` of one ``op_name`` path, such as
    ``jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/
    rematted_computation/otpu_mla/dot_general``: the ``vocabulary``
    names among its components in order, also inside ``jvp(...)`` and
    ``transpose(jvp(...))`` (not a ``jit(...)``'s own name, nor a Pallas
    kernel's before its ``pallas_call``);
    ``update`` under one of ``UPDATE_SCOPES``, else ``remat`` where the
    path holds ``rematted_computation`` (a checkpointed layer's forward
    pass run again in its backward pass), else ``backward`` where it
    holds ``transpose(``, else ``forward`` (which so means "neither of
    the three": the first forward pass, and what a step computes beside
    its gradients); and the ``otpu_*`` components that are no
    vocabulary name.  Where the compiler folded one instruction into
    another it joins their paths with ``;``: the first is read."""
    chain, unknown = [], []
    path = path.split(";")[0]
    parts = path.split("/")
    for part, after in zip(parts, parts[1:] + [""]):
        # a jit's own name, and a Pallas kernel's (the component before
        # its pallas_call), are no scopes
        if part.startswith(("jit(", "pjit(")) \
                or after.startswith("pallas_call"):
            continue
        for name in _SCOPE_NAME_RE.findall(part):
            # a checkpointed layer's recomputation repeats the scopes it
            # was traced under: once is enough
            if name not in chain:
                (chain if name in vocabulary else unknown).append(name)
    if any(name in UPDATE_SCOPES for name in chain):
        which = "update"
    elif "rematted_computation" in path:
        which = "remat"
    elif "transpose(" in path:
        which = "backward"
    else:
        which = "forward"
    return chain, which, unknown


def scope_map(compiled_text: str, vocabulary=STEP_SCOPES) -> dict:
    """Which instruction of a compiled program belongs to which scope:
    ``{"module": name, "ops": {instruction: {"chain": [...], "pass":
    ..., "mixed": bool, "inherited": bool, "opcode": ..., "kinds":
    [...] where mixed}}, "unknown": [...]}`` from the optimised HLO text
    (``jitted.lower(...).compile().as_text()``).

    ``chain`` and ``pass`` are :func:`scope_of_path` of the
    instruction's ``op_name``.  A fusion carries its root's, and is
    ``mixed`` where the instructions of its fused computation (those
    that run something and have an ``op_name``; a fusion inside it
    counts by its own) carry more than one innermost scope or more than
    one pass (``kinds`` lists them as ``scope:pass``): its seconds are
    booked to the root's scope and partly belong elsewhere.  The instructions inside fused computations are
    not listed: a trace has one op a fusion.

    The compiler makes instructions of its own, with no ``op_name`` or
    one that has lost its scopes: copies for a layout, a parameter's
    cast hoisted out of a loop, the TPU's ``ragged-dot`` custom call
    (its ``op_name`` is its own name).  Such an instruction is
    ``inherited``: it takes the chain most of its neighbours have
    (operands and users that have one, reached through other such
    instructions but not through a tuple, a loop or a call; an
    ``otpu_cast`` at a neighbour's end left off) and, having no path at
    all, the pass of its latest operand, or of its earliest user where
    no operand has one.  One that finds no neighbour keeps an empty
    chain, and ``pass`` None if it has no path.  ``unknown``: ``otpu_*`` path components outside the
    vocabulary.  A pure function of the text."""
    module = _HLO_MODULE_RE.search(compiled_text)
    computations: dict = {}     # name -> [(instruction, opcode, op_name,
    current = None              #           called, is root, operands)]
    for line in compiled_text.splitlines():
        if current is None:
            m = _HLO_COMPUTATION_RE.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _HLO_INSTRUCTION_RE.match(line)
        if not m:
            continue
        path = _HLO_OP_NAME_RE.search(line)
        called = _HLO_CALLS_RE.search(line)
        # a path of the program's starts at its jit: "jit(f)/..."; the
        # compiler's own ("ragged-dot-none") counts as none
        current.append((m.group(2), m.group(3),
                        path.group(1) if path and "/" in path.group(1)
                        else "",
                        called.group(1) if called else None,
                        bool(m.group(1)),
                        _HLO_OPERAND_RE.findall(line[m.end():])))

    def leaves(name):
        """(opcode, path, is root) of what a fused computation runs, a
        fusion nested in it replaced by its own."""
        for _, opcode, path, called, root, _ in computations.get(name, ()):
            if opcode == "fusion" and not path:
                yield from ((o, p, root and r) for o, p, r in leaves(called))
            else:
                yield opcode, path, root

    # a fusion's computation, and one applied element by element (a
    # reduction's, a sort's comparison), run inside their caller's op
    inside = {called for rows in computations.values()
              for _, opcode, _, called, _, _ in rows
              if called and opcode != "call"}
    ops: dict = {}
    unknown: set = set()
    operands: dict = {}
    users: dict = {}
    for name, rows in computations.items():
        if name in inside:
            continue
        for instruction, opcode, path, called, _, reads in rows:
            inner = list(leaves(called)) if opcode == "fusion" else ()
            if not path:        # a fusion without metadata: its root's
                path = next((p for _, p, root in inner if root), "")
            chain, which, other = scope_of_path(path, vocabulary)
            unknown.update(other)
            kinds = set()
            for o, p, _ in inner:
                if p and o not in TRIVIAL_OPCODES:
                    c, w, _ = scope_of_path(p, vocabulary)
                    kinds.add((c[-1] if c else None, w))
            ops[instruction] = {"chain": chain,
                                "pass": which if path else None,
                                "mixed": len(kinds) > 1, "inherited": False,
                                "opcode": opcode}
            if len(kinds) > 1:      # what it mixes: "scope:pass", sorted
                ops[instruction]["kinds"] = sorted(
                    f"{c or '-'}:{w}" for c, w in kinds)
            operands[instruction] = reads
            for read in reads:
                users.setdefault(read, []).append(instruction)
    # the compiler's own instructions take their neighbours' scopes, a
    # ring of neighbours a round
    def named(names):
        return [ops[n] for n in names if n in ops and ops[n]["chain"]
                and ops[n]["opcode"] not in _NO_INHERITANCE]

    def without_cast(chain):    # what reads a cast parameter is no cast
        return tuple(chain[:-1] if chain[-1] == "otpu_cast" else chain)

    in_order = (None,) + PASSES
    lost = [i for i, e in ops.items()
            if not e["chain"] and e["opcode"] not in _NO_INHERITANCE]
    while lost:
        found = {}
        for i in lost:
            reads, read_by = named(operands[i]), named(users.get(i, ()))
            if not reads and not read_by:
                continue
            # the chain most of its neighbours have (the first of equals,
            # operands before users); the pass of its latest operand, an
            # instruction running no earlier, or of its earliest user
            votes: dict = {}
            for near in reads + read_by:
                key = without_cast(near["chain"])
                votes[key] = votes.get(key, 0) + 1
            passes = [in_order.index(near["pass"])
                      for near in reads or read_by]
            found[i] = (list(max(votes, key=votes.get)),
                        in_order[max(passes) if reads else min(passes)])
        if not found:
            break
        for i, (chain, which) in found.items():
            ops[i].update(chain=chain, inherited=True,
                          **({} if ops[i]["pass"] else {"pass": which}))
        lost = [i for i in lost if i not in found]
    return {"module": module.group(1) if module else "", "ops": ops,
            "unknown": sorted(unknown)}


def reset_for_testing() -> None:
    """Drop all tracer state and re-arm from the cvar (tests only)."""
    global _ring, _ring_n, _slot, enabled, flow_enabled, requests_enabled
    with _hist_lock:
        _hist.clear()
    _ring = None
    _ring_n = 0
    _slot = itertools.count()
    _coll_seq.clear()
    enabled = False
    flow_enabled = False
    requests_enabled = False
    _set_enabled(bool(_enable_var.value))
