"""Software performance counters (``ompi/runtime/ompi_spc.c`` — inline
counters bumped in the bindings, exported as MPI_T-style pvars)."""
from __future__ import annotations

from ompi_tpu.base.var import PvarClass, registry

_COUNTERS = (
    "send", "isend", "recv", "irecv", "sendrecv", "probe", "iprobe",
    "bcast", "reduce", "allreduce", "gather", "scatter", "allgather",
    "alltoall", "reduce_scatter", "scan", "exscan", "barrier",
    "ibcast", "iallreduce", "ibarrier",
    "bytes_sent", "bytes_received", "bytes_packed", "bytes_unpacked",
    "unexpected_msgs", "out_of_sequence_msgs", "matched_msgs",
    "rget_msgs", "striped_msgs",
    "part_pready", "part_parrived", "part_msgs", "part_bytes",
    # programs a partitioned collective launched (mca/part/pcoll): one a
    # group of buckets, so over part_pready it says how often grouping
    # engages (1.0 where every Pready is its own launch)
    "part_group_launches",
    "device_collectives", "device_bytes",
    # coll/xla program cache, off the hot path (a cache hit in _fast
    # bumps none of them): calls that left _fast for _get, programs
    # built on a miss, and the host microseconds their first calls took
    # (trace, lower, compile or cache load, first dispatch) — set-up
    # runs before any profiler session, so counters cover what the
    # otpu.coll.get/build/first_call spans cannot
    "device_slow_path", "device_program_builds",
    "device_program_first_call_us",
    # the build record (runtime/trace.bind_builds), from JAX's own compile
    # events and recorded by its listener alone, so only while something
    # is built: the host microseconds the device path's own programs
    # (``trace.own_program``: otpu_* and trace.OWN_PROGRAMS) spent being
    # traced, lowered, and in the backend (a compile on a miss of the
    # persistent cache, a load on a hit), each program's outermost phase
    # once; their requests to the persistent cache and its hits; the
    # backend events themselves (every program of every build site, a
    # rebuild for new shapes too); and all three phases of everything
    # else the process built (a caller's programs, eager operations)
    "device_program_trace_us", "device_program_lower_us",
    "device_program_backend_us", "device_program_cache_requests",
    "device_program_cache_hits", "device_programs_compiled",
    "device_other_build_us",
    # the datatype engine's device path (datatype/plan behind
    # mca/accelerator): pack_array / unpack_array calls, the bytes of
    # their packed streams, plans built (one a datatype and count, so
    # none once a loop is warm), how many of those were index lists, how
    # many of the index lists pack by streaming their span (sorted, dense,
    # 4-byte) and the pack_array calls that ran that kernel
    "device_ddt_packs", "device_ddt_unpacks", "device_ddt_bytes",
    "device_ddt_plan_builds", "device_ddt_index_plans",
    "device_ddt_stream_plans", "device_ddt_stream_packs",
    # fastpath counters: the zero-copy host-datapath contract, pinned by
    # test_perf_guard (payload copies on the contiguous tcp send path
    # must stay 0; the schedule cache must hit on repeated collectives)
    "fastpath_hdr_fast", "fastpath_hdr_pickle", "fastpath_sendmsg",
    "fastpath_payload_copies",
    "fastpath_sched_hits", "fastpath_sched_misses", "fastpath_eager_lane",
    "fastpath_staging_hits", "fastpath_staging_misses",
    # native-reactor progress engine (runtime/reactor): non-empty record
    # drains per tick, fast-lane frags parsed natively, and slow-lane
    # frames forwarded to the Python _parse_frame — the frags/raw split
    # shows how much of the receive path actually ran off-GIL.  All
    # three stay EXACTLY flat with otpu_progress_native=0 (identity pin
    # in test_perf_guard).
    "progress_native_drains", "fastpath_native_frags",
    "fastpath_native_raw",
    # serving counters (ompi_tpu/serving): continuous-batching engine
    # admissions/evictions per tick, decoded token volume, KV-slab
    # streaming epochs, and requests requeued by serve-through-failure
    "serve_requests", "serve_tokens", "serve_ticks", "serve_admitted",
    "serve_evicted", "serve_requeued", "serve_kv_epochs", "serve_scaleups",
    # fleet counters (ompi_tpu/serving/fleet + prefix_cache): full
    # prefill passes actually computed, prefix-cache routing hits
    # (worker-verified, prefill skipped), router-side lookup misses,
    # stale hints (registry said hit, worker store said no — perf miss
    # by design), and telemetry-policy scale-downs/re-enlistments
    "serve_prefills", "serve_prefix_hits", "serve_prefix_misses",
    "serve_prefix_stale", "serve_scaledowns", "serve_enlists",
    # chaos counters (ompi_tpu/ft/chaos): every injected fault is
    # counted, so a chaos run self-documents what it actually injected
    "chaos_drop", "chaos_delay", "chaos_dup", "chaos_corrupt",
    "chaos_reset", "chaos_stall", "chaos_disconnect", "chaos_kill",
    # self-healing coord/wire layer: reconnect-retry activity and
    # detected (checksummed) wire corruption
    "coord_reconnects", "coord_rpc_retries", "wire_cksum_fail",
    # native-reactor framing desync (a zero-length frame on the wire,
    # detected on the epoll thread and failed loudly on dispatch)
    "wire_desync",
    # live-telemetry plane (runtime/telemetry + runtime/flight):
    # samples published into the coord KV, crash dumps written
    "telemetry_samples", "flight_dumps",
    # otpu-prof sampling profiler (runtime/profile): frame-sample ticks
    "profile_samples",
    # otpu-crit causal flow layer (runtime/trace flow_start/flow_finish):
    # emitted message-flow halves — finish/start ratio is the cheap
    # live proxy for the merged-timeline link rate
    "flow_starts", "flow_finishes",
    # coll/quant block-scale codec (mca/coll/quant): encode/decode
    # invocations across all three datapaths (device, wire, KV), the
    # wire stage's measured byte savings (original minus encoded bytes
    # of every quantized tcp frame), and quant frames that failed to
    # decode on receive (its OWN counter — the crc did verify, so
    # folding it into wire_cksum_fail would misattribute the fault)
    "quant_encodes", "quant_decodes", "quant_wire_bytes_saved",
    "quant_wire_decode_fail",
    # otpu-req per-request tracing (runtime/trace requests layer):
    # requests whose causal chain was stamped, and per-request stage
    # spans emitted — both stay EXACTLY flat while otpu_trace_requests
    # is off (the zero-overhead identity pin)
    "req_traced", "req_stages",
    # SLO accounting (runtime/telemetry slo plane): completions beating
    # the otpu_serving_slo_p99_ms target vs breaching it — both inert
    # while no SLO target is set
    "slo_goodput", "slo_breaches",
    # MoE expert parallelism (parallel/moe): tokens entering the ragged
    # dispatch, tokens dropped by the capacity policy, and the
    # high-water per-step load-imbalance factor in milli-units
    # (max-expert-load / mean-load * 1000 — a gauge kept as a
    # monotonic high-water so the counter plane stays append-only)
    "moe_dispatch_tokens", "moe_dropped_tokens", "moe_imbalance_max",
    # a public model's train step (parallel/train.py's model path):
    # optimiser steps issued (tokens, routed slots, bias updates are each
    # a constant of the configuration times this), and the fullest
    # expert's slots in any step read back so far (a high-water gauge,
    # read outside the step: ``train.record_step_stats``)
    "train_steps", "moe_max_expert_load",
    # a rank that holds a share of the routed experts: over the steps
    # read back (``train_steps_read``), the slots that went to experts
    # held here and to absent ones, and the rows the held experts' loops
    # walked for the held ones (a layer's held slots in whole chunks of
    # ``experts.chunk_rows``): the first over the last is the share of
    # the rows walked that held a slot
    "train_steps_read", "moe_local_slots", "moe_absent_slots",
    "moe_chunk_rows",
    # what a built train step holds, fed ONCE a built step, at its first
    # call, from its plan (parallel/train.plan_of: Python over the
    # configuration and the shapes, by the decision functions the traced
    # code asks; no traced line records any of these).  A count is a layer
    # application in one forward pass of the step: a scanned run of three
    # layers counts three, a looped model's four passes four times its
    # layers, a custom_vjp's backward rule is no second application.
    # Each ``*_kernel_built`` over its ``*_built`` is the share of the
    # applications that took the Pallas kernels; ``step.plan()`` names
    # the clause that refused the others.
    #
    # the experts' grouped matmuls (parallel/experts: a gated expert's
    # three a layer, relu2's two) and those of them on the Pallas kernel
    # (ops/grouped_matmul): moe.gmm_kernel_share
    "moe_gmm_built", "moe_gmm_kernel_built",
    # the held experts' forward loops' row scatter-adds (parallel/
    # experts.local_expert_ffn: one a layer) and those of them on the
    # Pallas row kernel (ops/row_scatter): moe.scatter_kernel_share, which
    # waits for room in per_layer
    "moe_scatter_built", "moe_scatter_kernel_built",
    # the chunked delta rules (parallel/gdn: one a Gated DeltaNet layer)
    # and those of them on the Pallas kernels (ops/gated_delta):
    # gdn.kernel_share
    "gdn_rule_built", "gdn_rule_kernel_built",
    # the Mamba-2 scans (parallel/mamba: one a mixer) and those of them
    # on the Pallas kernels (ops/ssd_scan): ssm.kernel_share, waiting
    "ssm_scan_built", "ssm_scan_kernel_built",
    # the DeltaNet convolutions (parallel/gdn.gated_delta_net: one a
    # layer) and those of them on the Pallas kernels (ops/causal_conv):
    # gdn.conv_kernel_share
    "gdn_conv_built", "gdn_conv_kernel_built",
    # the feed-forwards that call ``layers.swiglu`` or ``relu2`` (a dense
    # layer's, a shared expert's: one a layer application) and those of
    # them whose backward pass is the written rule (parallel/layers.
    # ffn_bwd_written: all in bfloat16, none under a float32
    # ``compute_dtype``): ffn.bwd_written_share, waiting for room
    "ffn_built", "ffn_bwd_written_built",
    # the causal attention layers (parallel/causal.pass_counts), and those
    # of them whose k and v come with fewer heads than q and go to the
    # flash kernels, or their twins, unrepeated: attn.shared_kv_share, 100
    # for a grouped-query model, 0 for the rest
    "attn_built", "attn_shared_kv_built",
    # the q and k arrays a ``layer_types`` model's attention sublayers
    # make for the flash kernels, with a per-head QK-norm or without
    # (parallel/attention.normed_qk: two a layer), and those of them made
    # on the Pallas kernels (ops/head_norm_rope: norm, RoPE, head split
    # and cast in one pass): attn.qk_kernel_share, waiting
    "attn_qk_built", "attn_qk_kernel_built",
    # those of the attention layers under a sliding window
    # (attn.window_share), the block pairs the layers walk, and those
    # full causal layers of their lengths would: walked over causal is
    # what the windows and masks spare (attn.pairs_walked_share)
    "attn_window_built", "attn_pairs_walked", "attn_pairs_causal",
    # learned sparse attention (parallel/causal.selected_flash_attention):
    # the attention layers under a selection, the (query, key) pairs they
    # attend to and those full causal layers of their lengths would, both
    # from the shapes: selected over causal is what the selection leaves
    # of the triangle (dsa.selected_share); and the bytes of the selection
    # a layer reads, packed eight keys a byte: b x s x s / 8
    "dsa_built", "dsa_keys_selected", "dsa_keys_causal", "dsa_mask_bytes",
    # block diffusion (parallel/causal.block_diffusion_flash_attention):
    # the attention layers under its mask, the (query, key) pairs they
    # attend to over a noisy and a clean copy of every sequence and those
    # causal layers over the same rows would, both from the shapes
    # (bd.visible_share: 50.02% at 8,192 tokens in blocks of 4); and, read
    # back a step outside every window (parallel/train.record_step_stats),
    # the token rows the steps' noise replaced by the mask token
    "bd_built", "bd_pairs_visible", "bd_pairs_causal", "bd_rows_masked",
    # a looped model's walk (parallel/objective.loop_counts), from the
    # shapes: the looped steps built, the passes, the layers held, the
    # layer applications (passes x layers: over the layers held, the times
    # a leaf is read a pass of the step, loop.applications_per_layer) and
    # the rows the head reads (passes x tokens); and, read back a step
    # outside every window (parallel/train.record_step_stats), the batch's
    # mean exit pass sum_t t p_t in thousandths (1,875 at a gate of zero:
    # loop.exit_depth, waiting)
    "loop_built", "loop_passes", "loop_layers_held",
    "loop_layer_applications", "loop_head_rows", "loop_exit_depth",
    # the documents of a packed row (parallel/objective.documents): the
    # scans and convolutions (two a Mamba-2 mixer) and attention masks
    # (one a layer: parallel/causal.document_selection) made under a row's
    # documents; and, read back a step outside every window
    # (parallel/train.record_step_stats), the documents begun, the (query,
    # key) pairs one attention layer sees under the document mask and
    # those it would under the triangle alone: visible over causal is what
    # the boundaries leave of attention's work (doc.visible_share and
    # doc.starts_per_row, waiting)
    "doc_built", "doc_starts", "doc_pairs_visible", "doc_pairs_causal",
    # several residual streams (parallel/hyper.py: manifold-constrained
    # hyper-connections): the sublayer applications on a stream and the
    # Sinkhorn sweeps in them (hc_built x hc_sinkhorn_iters), both from the
    # plan; and, read back a step outside every window
    # (parallel/train.record_step_stats), the largest defect from doubly
    # stochastic of a sampled mixing map in any step read so far, in parts
    # per million: what the sweeps leave, a high-water gauge (hc.* metrics,
    # waiting for room in per_layer)
    "hc_built", "hc_sweeps_built", "hc_defect_ppm",
    # serving front door (serving/frontdoor) + speculative decode
    # (serving/worker): requests shed at admission with a retry-after,
    # batch-class decodes preempted back into the queue on an
    # interactive-p99 breach, and draft-model tokens the target model
    # accepted vs rejected in the batched verify step — all EXACTLY
    # flat while the front door / spec_k are off (identity pins in
    # test_perf_guard and test_frontdoor)
    "serve_shed", "serve_preempt", "serve_spec_accepts",
    "serve_spec_rejects",
)

_pvars = {}


def init() -> None:
    for name in _COUNTERS:
        _pvars[name] = registry.register_pvar(
            "runtime", "spc", name, pclass=PvarClass.COUNTER,
            help=f"SPC counter: number/volume of {name}")
    # device counters accumulate in module ints (bump_device) and fold in
    # lazily; the pre-read hook keeps direct pvar readers (otpu_info
    # --pvars via registry.all_pvars) coherent too
    for name in ("device_collectives", "device_bytes"):
        if name in _pvars:
            _pvars[name].on_read = _flush_device


def record(name: str, value: float = 1) -> None:
    pv = _pvars.get(name)
    if pv is not None:
        pv.add(value)


_dev_calls_n = 0
_dev_bytes_n = 0


def bump_device(nbytes: int, calls: int = 1) -> None:
    """Hot-path SPC bump for device collectives: two plain integer adds
    on module globals (folded into the pvars at read time), mirroring the
    reference's inline non-atomic counter increments (``ompi_spc.c`` —
    SPC counters are not atomic unless multithreaded accuracy is
    requested).  ``calls`` is the collectives one launch carries (a
    group of buckets of a partitioned collective): the counter counts
    collectives, not launches."""
    global _dev_calls_n, _dev_bytes_n
    _dev_calls_n += calls
    _dev_bytes_n += nbytes


def _flush_device() -> None:
    """Fold the relaxed device-counter accumulators into their pvars."""
    global _dev_calls_n, _dev_bytes_n
    if _dev_calls_n:
        pv = _pvars.get("device_collectives")
        if pv is not None:
            pv.add(_dev_calls_n)
            _dev_calls_n = 0
        pv = _pvars.get("device_bytes")
        if pv is not None:
            pv.add(_dev_bytes_n)
            _dev_bytes_n = 0


def read(name: str) -> float:
    pv = _pvars.get(name)
    return 0 if pv is None else pv.read()


def counters() -> dict:
    return {k: v.read() for k, v in _pvars.items()}
