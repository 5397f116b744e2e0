"""otpu-prof — stage clocks, the sampling profiler, and the analyzer's
host-overhead decomposition.

Four layers of coverage:

* stage-clock unit: declared-table enforcement, histogram math,
  snapshot/delta semantics, disabled identity;
* sampling-profiler unit: phases bucket through the @hot_path registry,
  GIL estimates are fractions, stop() restores the no-thread state;
* analyzer unit: decomposition buckets, exposed-host fraction, and
  stage-sum vs e2e reconciliation over a synthetic profile payload;
* THE acceptance run — a 3-rank loopback allreduce job with the stage
  clocks + profiler armed: the otpu_analyze report carries a per-rank
  exposed-host fraction and a pack/queue/wire/parse/deliver breakdown
  whose stage sums reconcile with the measured end-to-end collective
  latency (0 < stage_sum/e2e <= 1.25 — stages are work segments inside
  the e2e window; the remainder is progress-loop wait.  The upper slack
  absorbs cross-thread overlap: parse/deliver run on the progress
  thread inside the same window).
"""
import json
import os
import sys
import time
from pathlib import Path

import pytest

import launch

REPO = Path(__file__).resolve().parent.parent
WORKER = Path(__file__).resolve().parent / "telemetry_worker.py"


@pytest.fixture
def stage_clocks():
    from ompi_tpu.runtime import profile

    profile.reset_for_testing()
    profile._set_enabled(True)
    yield profile
    profile.reset_for_testing()


# ------------------------------------------------------ stage-clock unit

def test_stage_table_is_closed(stage_clocks):
    profile = stage_clocks
    t0 = profile.now()
    profile.stage_span("send.pack", t0)
    with pytest.raises(ValueError):
        profile.stage_span("not.a.stage", profile.now())
    with pytest.raises(ValueError):
        profile.stage_mark("not.a.stage")
    # every documented decomposition stage is declared
    for stage in ("send.pack", "send.staging", "send.queue", "send.wire",
                  "recv.parse", "recv.deliver", "recv.complete",
                  "coll.decide", "coll.alg"):
        assert stage in profile.STAGES, stage


def test_stage_histogram_math(stage_clocks):
    profile = stage_clocks
    base = profile.now()
    for us in (10, 20, 40):
        profile.stage_span("send.pack", base - us * 1000, base)
    stats = profile.stage_stats()["send.pack"]
    assert stats["n"] == 3
    assert stats["sum_us"] == pytest.approx(70.0, abs=0.5)
    assert stats["min_us"] == pytest.approx(10.0, abs=0.5)
    assert stats["max_us"] == pytest.approx(40.0, abs=0.5)
    assert stats["min_us"] <= stats["p50_us"] <= stats["p99_us"] \
        <= stats["max_us"]
    # delta API: only new occurrences appear, populations never reset
    snap = profile.stage_snapshot()
    profile.stage_span("send.pack", base - 5000, base)
    d = profile.stage_delta_stats(snap, profile.stage_snapshot())
    assert d["send.pack"]["n"] == 1
    assert profile.stage_stats()["send.pack"]["n"] == 4
    assert profile.stage_delta_stats(
        profile.stage_snapshot(), profile.stage_snapshot()) == {}


def test_stage_clock_disabled_identity():
    from ompi_tpu.runtime import profile

    profile.reset_for_testing()
    assert profile.enabled is False
    # disabled: nothing records, even with a bogus name (no table walk)
    profile.stage_span("not.a.stage", 12345)
    profile.stage_mark("not.a.stage")
    assert profile.stage_snapshot() == {}
    # a begin captured before a mid-run enable must not record garbage
    profile._set_enabled(True)
    try:
        profile.stage_span("send.pack", 0)
        assert profile.stage_snapshot() == {}
    finally:
        profile.reset_for_testing()


# ------------------------------------------------- sampling-profiler unit

def test_profiler_phases_and_gil_estimates():
    import threading

    from ompi_tpu.runtime import hotpath, profile

    profile.reset_for_testing()

    @hotpath.hot_path
    def _prof_test_spin():
        deadline = time.monotonic() + 0.6
        x = 0
        while time.monotonic() < deadline:
            x += 1
        return x

    p = profile.HostProfiler(rank=0, interval_ms=5)
    with profile._lock:
        profile._profiler = p
    try:
        p.start()
        t = threading.Thread(target=_prof_test_spin)
        t.start()
        t.join()
        time.sleep(0.05)
        stats = profile.profiler_stats()
        assert stats is not None and stats["samples"] > 10
        # the spin thread's frames bucket under its @hot_path name
        assert any("_prof_test_spin" in k for k in stats["phases"]), \
            stats["phases"]
        assert 0.0 <= stats["gil_released"] <= 1.0
        assert 0.0 <= stats["gil_wait"] <= 1.0
        # the pytest main thread sits in threading.join -> GIL released
        assert stats["phases"].get("idle", 0) > 0, stats["phases"]
    finally:
        profile.reset_for_testing()
    assert not [th for th in threading.enumerate()
                if th.name == "otpu-prof"], "profiler thread survived"


def test_profiler_stop_clears_slot_for_reinit():
    """stop() must clear the profiler slot (the telemetry.stop
    discipline): a finalize/init cycle re-arms a FRESH sampler instead
    of early-returning against a dead thread whose frozen estimates
    would read as live."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import profile

    profile.reset_for_testing()
    registry.lookup("otpu_profile_interval_ms").set(5)

    class _Rte:
        my_world_rank = 0

    try:
        assert profile.start(_Rte()) is True
        p1 = profile._profiler
        assert p1 is not None
        profile.stop()
        assert profile._profiler is None
        assert profile.start(_Rte()) is True
        p2 = profile._profiler
        assert p2 is not p1 and p2._thread.is_alive()
    finally:
        registry.lookup("otpu_profile_interval_ms").set(0)
        profile.reset_for_testing()


def test_export_payload_shape(stage_clocks):
    profile = stage_clocks
    assert profile.export_payload() is not None   # stages armed, empty
    profile.stage_span("coll.alg", profile.now() - 1000)
    payload = profile.export_payload()
    assert "stages" in payload and "coll.alg" in payload["stages"]
    # the armed plane reports its own covered window — the analyzer's
    # ring-overwrite-immune exposed-host denominator
    assert payload["elapsed_us"] > 0
    # JSON-serializable end to end (rides in trace metadata / flight)
    json.dumps(payload)


# ------------------------------------------------------- analyzer unit

def _mk_profile(scale=1.0):
    mk = lambda n, mean: {"n": n, "sum_us": round(n * mean * scale, 1),
                          "mean_us": round(mean * scale, 2),
                          "min_us": 1.0, "max_us": 2 * mean}
    return {"stages": {
        "send.pack": mk(10, 8.0), "send.queue": mk(10, 5.0),
        "send.wire": mk(12, 30.0), "recv.parse": mk(10, 15.0),
        "recv.deliver": mk(10, 35.0), "recv.complete": mk(10, 4.0),
    }, "profiler": {"samples": 40, "phases": {"idle": 30},
                    "gil_released": 0.7, "gil_wait": 0.1}}


def _synthetic_events(rounds=10, ranks=3, dur=600.0):
    events = []
    t = 0.0
    for _ in range(rounds):
        for r in range(ranks):
            events.append({"ph": "X", "cat": "coll", "name": "allreduce",
                           "ts": t + r * 10.0, "dur": dur, "pid": r,
                           "args": {"cid": 0, "nbytes": 4096}})
        t += 5000.0
    return sorted(events, key=lambda e: e["ts"])


def test_analyze_host_overhead_decomposition():
    from ompi_tpu.tools import otpu_analyze

    events = _synthetic_events()
    profiles = {r: _mk_profile() for r in range(3)}
    rep = otpu_analyze.analyze(events, profiles=profiles)
    oh = rep["host_overhead"]
    assert set(oh) == {"0", "1", "2"}
    row = oh["0"]
    d = row["decomposition"]
    assert set(d) == {"pack", "queue", "wire", "parse", "deliver"}
    assert d["pack"]["mean_us"] == pytest.approx(8.0)
    assert d["deliver"]["total_us"] == pytest.approx(390.0)  # 350+40
    # host stages exclude the wire bucket
    assert row["host_stage_us"] == pytest.approx(
        row["stage_sum_us"] - d["wire"]["total_us"])
    # reconciliation: e2e = 10 rounds x 600us
    assert row["coll_e2e_us"] == pytest.approx(6000.0)
    assert 0.0 < row["stage_over_e2e"] <= 1.25
    assert 0.0 < row["exposed_host_fraction"] < 1.0
    assert row["profiler"]["gil_released"] == 0.7
    # the profile's own covered window wins over the ring-limited
    # trace window (long-run honesty: stage totals span the whole run,
    # the surviving trace events may not)
    prof_w = _mk_profile()
    prof_w["elapsed_us"] = 1e9
    rep_w = otpu_analyze.analyze(events, profiles={0: prof_w})
    assert rep_w["host_overhead"]["0"]["exposed_host_fraction"] < \
        row["exposed_host_fraction"]
    # diff flags exposed-host movement
    rep2 = otpu_analyze.analyze(
        events, profiles={r: _mk_profile(scale=2.0) for r in range(3)})
    delta = otpu_analyze.diff_reports(rep, rep2)
    assert delta["exposed_host_delta"]["0"] > 0
    # both render modes carry the section
    text = otpu_analyze.render_text(rep)
    assert "host-overhead decomposition" in text
    parsable = otpu_analyze.render_text(rep, parsable=True)
    assert any(ln.startswith("exposed_host:0:")
               for ln in parsable.splitlines())
    assert any(ln.startswith("host_stage:0:pack:")
               for ln in parsable.splitlines())


def test_load_run_collects_profiles(tmp_path):
    from ompi_tpu.tools import otpu_analyze

    events = _synthetic_events(rounds=3)
    for r in range(3):
        mine = [e for e in events if e["pid"] == r]
        (tmp_path / f"trace_rank{r}.json").write_text(json.dumps(
            {"traceEvents": mine,
             "metadata": {"rank": r, "clock_offset_us": 0.0,
                          "profile": _mk_profile()}}))
    # a merged file alongside: events prefer it, profiles still load
    (tmp_path / "trace_merged.json").write_text(
        json.dumps({"traceEvents": events}))
    ev, profiles, _meta = otpu_analyze.load_run([str(tmp_path)])
    assert len(ev) == len(events)
    assert set(profiles) == {0, 1, 2}
    rep = otpu_analyze.analyze(ev, profiles=profiles)
    assert set(rep["host_overhead"]) == {"0", "1", "2"}


# ------------------------------------------------- THE acceptance run

def test_stage_breakdown_reconciles_on_loopback_allreduce(tmp_path):
    """3-rank loopback allreduce job, stage clocks + profiler armed:
    the analyzer report has a per-rank exposed-host fraction and a
    five-bucket decomposition whose stage sums reconcile with measured
    end-to-end latency (see module docstring for the band)."""
    tdir = tmp_path / "trace"
    env = dict(os.environ, JAX_PLATFORMS="cpu", TW_ITERS="30")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
           "--mca", "otpu_trace_enable", "1",
           "--mca", "otpu_trace_dir", str(tdir),
           "--mca", "otpu_profile_stages", "1",
           "--mca", "otpu_profile_interval_ms", "10",
           # coll/sm below tuned so the collectives cross the pml/btl
           # datapath the stage clocks instrument
           "--mca", "otpu_coll_sm_coll_priority", "0",
           sys.executable, str(WORKER)]
    r = launch.run(cmd, 300, env)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    from ompi_tpu.tools import otpu_analyze

    events, profiles, _meta = otpu_analyze.load_run([str(tdir)])
    assert set(profiles) == {0, 1, 2}, (sorted(profiles), out)
    rep = otpu_analyze.analyze(events, profiles=profiles)
    assert rep["rounds_total"] >= 25, rep["rounds_total"]
    oh = rep["host_overhead"]
    assert set(oh) == {"0", "1", "2"}
    for rank, row in oh.items():
        d = row["decomposition"]
        # every bucket of the per-message breakdown is populated
        for bucket in ("pack", "queue", "wire", "parse", "deliver"):
            assert bucket in d, (rank, sorted(d))
            assert d[bucket]["n"] >= 25, (rank, bucket, d[bucket])
            assert d[bucket]["mean_us"] > 0
        # reconciliation: stage sums are work inside the e2e window
        assert row["coll_e2e_us"] > 0
        assert 0.0 < row["stage_over_e2e"] <= 1.25, (rank, row)
        # exposed-host fraction present and sane
        assert 0.0 < row["exposed_host_fraction"] < 1.0, (rank, row)
        # the sampling profiler rode along
        assert row["profiler"]["samples"] > 0, (rank, row)
        assert 0.0 <= row["profiler"]["gil_released"] <= 1.0
