"""otpu-trace: disabled-path no-op, span/histogram correctness under
concurrency, Chrome-JSON schema validity, and the tpurun gather/merge +
skew report on a real multiprocess run."""
import json
import os
import sys
import textwrap
import threading

import numpy as np
import pytest

from ompi_tpu.base.var import registry
from ompi_tpu.runtime import trace

import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracer():
    """Enabled tracer with clean state; disabled + reset afterwards."""
    registry.set("otpu_trace_enable", True)
    trace.reset_for_testing()
    yield trace
    registry.set("otpu_trace_enable", False)
    trace.reset_for_testing()


class _FakeComm:
    cid = 42

    def __init__(self):
        self.c_coll = {}


def test_disabled_path_records_nothing():
    registry.set("otpu_trace_enable", False)
    trace.reset_for_testing()
    before = trace.recorded_count()
    trace.span("x", "coll", trace.now())
    trace.instant("y", "ft")
    assert trace.recorded_count() == before
    assert trace.enabled is False

    # the coll-table wrapper passes straight through and records nothing
    comm = _FakeComm()
    comm.c_coll["allreduce"] = lambda c, x: x * 2
    trace.wrap_coll_table(comm)
    out = comm.c_coll["allreduce"](comm, np.ones(4))
    assert np.all(out == 2)
    assert trace.histograms() == {}
    assert trace.recorded_count() == 0


def test_wrapper_records_span_and_histogram(tracer):
    comm = _FakeComm()
    comm.c_coll["allreduce"] = lambda c, x: x + 1
    trace.wrap_coll_table(comm)
    # double-wrap guard: wrapping again must not stack another layer
    wrapped = comm.c_coll["allreduce"]
    trace.wrap_coll_table(comm)
    assert comm.c_coll["allreduce"] is wrapped

    x = np.ones(1 << 12, np.float32)          # 16384 B -> "16k" bin
    for _ in range(5):
        comm.c_coll["allreduce"](comm, x)
    hists = trace.histograms()
    assert ("allreduce", "16k") in hists
    count, sum_us, min_us, max_us = hists[("allreduce", "16k")]
    assert count == 5
    assert 0 <= min_us <= max_us
    assert sum_us >= 5 * min_us
    # the same data is live through the MPI_T pvar surface
    pvs = {p.name: p for p in registry.all_pvars()}
    assert pvs["otpu_trace_hist_allreduce_16k_count"].read() == 5
    assert pvs["otpu_trace_hist_allreduce_16k_sum_us"].read() > 0
    # spans landed in the ring with the comm's cid
    spans = [e for e in trace.chrome_events() if e["name"] == "allreduce"]
    assert len(spans) == 5
    assert all(e["args"]["cid"] == 42 for e in spans)


def test_concurrent_recording_is_consistent(tracer):
    per_thread, nthreads = 500, 4

    def worker(i):
        for k in range(per_thread):
            t0 = trace.now()
            trace.span(f"op{i}", "coll", t0)
            trace.hist_record("allreduce", 1024, 1000)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # histogram updates are exact (locked)
    assert trace.histograms()[("allreduce", "1k")][0] == \
        per_thread * nthreads
    # every span got its own ring slot (atomic slot counter)
    assert trace.recorded_count() == per_thread * nthreads
    events = trace.chrome_events()
    assert len(events) == per_thread * nthreads


def test_hist_percentile_interpolates_log2_bins(tracer):
    # a known latency population: 90 fast (1us) + 10 slow (1ms) calls.
    # p50 must land in the fast bin, p99 in the slow bin — each within
    # its log2 bin (the estimator's contract), clamped to observed
    # min/max.
    for _ in range(90):
        trace.hist_record("serve_request", 256, 1_000)
    for _ in range(10):
        trace.hist_record("serve_request", 256, 1_000_000)
    p50 = trace.hist_percentile("serve_request", 0.5)
    p99 = trace.hist_percentile("serve_request", 0.99)
    assert 1.0 <= p50 <= 2.0, p50          # us; fast bin [512ns, 1024ns]+clamp
    assert 512.0 <= p99 <= 1048.0, p99     # us; slow bin [2^19, 2^20) ns
    assert p50 <= trace.hist_percentile("serve_request", 0.9) <= p99

    # single-bin population: clamping pins the estimate to observed range
    for _ in range(10):
        trace.hist_record("one_bin", 8, 700)
    assert trace.hist_percentile("one_bin", 0.99) == pytest.approx(
        0.7, abs=0.3)


def test_hist_percentile_merges_size_bins_and_filters(tracer):
    trace.hist_record("bcast", 64, 10_000)        # 64b size bin, 10us
    trace.hist_record("bcast", 1 << 20, 90_000)   # 1m size bin, 90us
    # per-size-bin query sees only its own cell
    assert trace.hist_percentile("bcast", 0.5, nbytes=64) < 20.0
    assert trace.hist_percentile("bcast", 0.5, nbytes=1 << 20) > 60.0
    # merged query spans both; an unknown coll reports 0
    merged = trace.hist_percentile("bcast", 0.99)
    assert merged >= 64.0
    assert trace.hist_percentile("nope", 0.5) == 0.0
    with pytest.raises(ValueError):
        trace.hist_percentile("bcast", 1.5)


def test_hist_reset_starts_fresh_population(tracer):
    for _ in range(50):
        trace.hist_record("serve_request", 64, 1_000_000)   # 1ms
    assert trace.hist_percentile("serve_request", 0.5) > 500.0
    trace.hist_reset("serve_request")
    assert trace.hist_percentile("serve_request", 0.5) == 0.0
    trace.hist_record("serve_request", 64, 1_000)           # 1us
    assert trace.hist_percentile("serve_request", 0.99) < 10.0
    # other collectives' cells survive the reset
    trace.hist_record("bcast", 64, 5_000)
    trace.hist_reset("serve_request")
    assert trace.hist_percentile("bcast", 0.5) > 0.0


def test_hist_percentile_pvars_via_read_path(tracer):
    for d in (1_000, 2_000, 4_000, 1_000_000):
        trace.hist_record("allreduce", 4096, d)
    by_name = {p.name: p for p in registry.all_pvars()}
    pv50 = by_name.get("otpu_trace_hist_allreduce_4k_p50_us")
    pv99 = by_name.get("otpu_trace_hist_allreduce_4k_p99_us")
    assert pv50 is not None and pv99 is not None
    v50, v99 = pv50.read(), pv99.read()
    assert 0 < v50 < v99 <= 1000.0
    assert v99 > 100.0      # pulled toward the 1ms outlier


def test_ring_overwrites_oldest(tracer):
    n = trace._ring_n
    for i in range(n + 100):
        trace.span(f"s{i}", "coll", trace.now())
    events = trace.chrome_events()
    assert len(events) == n
    payload = trace.chrome_payload(0)
    assert payload["metadata"]["events_overwritten"] == 100


def test_chrome_json_schema(tracer):
    t0 = trace.now()
    trace.span("allreduce", "coll", t0, args={"nbytes": 64})
    trace.instant("ft_detect", "ft", args={"rank": 1})
    payload = trace.chrome_payload(3, clock_offset_us=12.5)
    # must survive a JSON round-trip (what finalize writes to disk)
    payload = json.loads(json.dumps(payload))
    assert set(payload) == {"traceEvents", "metadata"}
    meta = payload["metadata"]
    assert meta["rank"] == 3
    assert meta["clock_offset_us"] == 12.5
    evs = payload["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float)
        assert ev["pid"] == 3
        assert isinstance(ev["tid"], int)
        assert ev["name"] and ev["cat"]
    x = [e for e in evs if e["ph"] == "X"][0]
    assert x["dur"] >= 0
    # events come out oldest-first
    assert evs[0]["ts"] <= evs[1]["ts"]


def _payload(rank, offset_us, spans):
    return {
        "traceEvents": [
            {"ph": "X", "name": name, "cat": "coll", "ts": ts,
             "dur": dur, "pid": rank, "tid": 1,
             "args": {"nbytes": nbytes}}
            for name, ts, dur, nbytes in spans],
        "metadata": {"rank": rank, "clock_offset_us": offset_us},
    }


def test_merge_handles_negative_offsets_and_zero_span_ranks():
    """Crash-bundle shapes: a rank whose clock ran BEHIND the coord's
    (negative offset) must align onto the same timebase, and a rank
    whose payload has zero spans must neither crash the merge/skew path
    nor erase the other ranks' matched rounds."""
    # rank 0 runs 500us behind the coord clock (offset is ours MINUS
    # the coord's, so it is negative); rank 1 runs 250us ahead
    p0 = _payload(0, -500.0, [("allreduce", -400.0, 50.0, 1024)])
    p1 = _payload(1, 250.0, [("allreduce", 350.0, 80.0, 1024)])
    p2 = _payload(2, 100.0, [])               # zero spans (died early)
    merged = trace.merge_timelines([p0, p1, p2])
    assert [e["ts"] for e in merged] == [100.0, 100.0]
    assert sorted(e["pid"] for e in merged) == [0, 1]

    report = trace.skew_report([p0, p1, p2])
    # the zero-span rank must NOT zero the survivors' rounds (the
    # pre-fix behavior: min over ALL ranks made every round unmatched)
    line = next(ln for ln in report.splitlines()
                if ln.startswith("allreduce"))
    cols = line.split()
    assert cols[2] == "1", line               # one matched round
    assert cols[5] == "1", line               # rank 1's 80us is slowest
    assert "absent" in line                   # the dead rank is noted
    assert "3 ranks" in report


def test_flow_events_survive_merge_and_export(tracer):
    trace.flow_start("pml_msg", (3, 0, 1, 9))
    trace.flow_finish("pml_msg", (3, 0, 1, 9))
    payload = trace.chrome_payload(1, clock_offset_us=-40.0)
    payload = json.loads(json.dumps(payload))
    merged = trace.merge_timelines([payload])
    flows = [e for e in merged if e["ph"] in ("s", "f")]
    assert len(flows) == 2
    assert all(e["id"] == "3.0.1.9" for e in flows)
    assert all(e["pid"] == 1 for e in flows)
    # alignment shifted the flow timestamps like any span's
    raw = [e for e in payload["traceEvents"] if e["ph"] in ("s", "f")]
    assert flows[0]["ts"] == raw[0]["ts"] + 40.0


def test_merge_aligns_clocks_and_skew_names_slowest():
    # rank 1's clock runs 1000us ahead of the coord clock; after merge
    # both ranks' allreduces line up at ts=100
    p0 = _payload(0, 0.0, [("allreduce", 100.0, 50.0, 1024)])
    p1 = _payload(1, 1000.0, [("allreduce", 1100.0, 400.0, 1024)])
    merged = trace.merge_timelines([p0, p1])
    assert [e["ts"] for e in merged] == [100.0, 100.0]
    assert sorted(e["pid"] for e in merged) == [0, 1]

    report = trace.skew_report([p0, p1])
    assert "allreduce" in report
    # rank 1's 400us invocation is the straggler (columns: name cid
    # rounds spread_mean spread_max slowest_rank)
    line = next(ln for ln in report.splitlines()
                if ln.startswith("allreduce"))
    assert line.split()[5] == "1"
    assert "p50_us" in report and "1k" in report


def test_boot_path_spans_in_merged_timeline(tmp_path):
    """The instance boot path is spanned — coord connect, jax
    distributed init slot, modex fence, and the whole instance_boot —
    so cross-rank merged timelines show STARTUP skew, not just
    steady-state collective skew."""
    script = tmp_path / "boot_traced.py"
    script.write_text(textwrap.dedent("""
        import ompi_tpu
        w = ompi_tpu.init()
        w.barrier()
        ompi_tpu.finalize()
    """))
    tdir = tmp_path / "traces"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "2",
         "--mca", "trace_enable", "1", "--mca", "trace_dir", str(tdir),
         sys.executable, str(script)],
        240, env)
    assert r.returncode == 0, r.stdout + r.stderr
    for rank in range(2):
        p = json.load(open(tdir / f"trace_rank{rank}.json"))
        boots = {e["name"] for e in p["traceEvents"]
                 if e["cat"] == "boot"}
        assert {"coord_connect", "jax_distributed_init", "modex_fence",
                "instance_boot"} <= boots, boots
        # the whole-boot span encloses the fence span
        span_of = {e["name"]: e for e in p["traceEvents"]
                   if e["cat"] == "boot"}
        whole, fence = span_of["instance_boot"], span_of["modex_fence"]
        assert whole["ts"] <= fence["ts"]
        assert whole["ts"] + whole["dur"] >= fence["ts"] + fence["dur"]
    merged = json.load(open(tdir / "trace_merged.json"))
    boot_pids = {e["pid"] for e in merged["traceEvents"]
                 if e.get("cat") == "boot"}
    assert boot_pids == {0, 1}


def test_tpurun_trace_gather_merge_and_skew(tmp_path):
    """4-rank end-to-end: per-rank Chrome JSON, merged timeline, skew
    report — the full gather path through the CoordServer."""
    script = tmp_path / "traced.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu, time
        w = ompi_tpu.init()
        for _ in range(4):
            w.allreduce(np.ones(4096, np.float32))
        if w.rank == w.size - 1:
            time.sleep(0.02)          # deliberate straggler
        w.barrier()
        ompi_tpu.finalize()
    """))
    tdir = tmp_path / "traces"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "4",
         "--mca", "trace_enable", "1", "--mca", "trace_dir", str(tdir),
         sys.executable, str(script)],
        240, env)
    assert r.returncode == 0, r.stdout + r.stderr

    # per-rank Chrome traces
    for rank in range(4):
        p = json.load(open(tdir / f"trace_rank{rank}.json"))
        assert p["metadata"]["rank"] == rank
        colls = [e for e in p["traceEvents"] if e["cat"] == "coll"]
        assert any(e["name"] == "allreduce" for e in colls)
        assert all(e["pid"] == rank for e in p["traceEvents"])

    # merged timeline: all four pids, time-sorted
    merged = json.load(open(tdir / "trace_merged.json"))
    evs = merged["traceEvents"]
    assert sorted({e["pid"] for e in evs}) == [0, 1, 2, 3]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)

    # skew report names a slowest rank per collective
    report = (tdir / "trace_skew.txt").read_text()
    assert "allreduce" in report and "slowest_rank" in report
    line = next(ln for ln in report.splitlines()
                if ln.startswith("allreduce"))
    assert int(line.split()[5]) in (0, 1, 2, 3)
