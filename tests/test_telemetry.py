"""otpu-top — the live telemetry plane, flight recorder, and analyzer.

Five layers of coverage:

* trace snapshot/delta API: sampling never disturbs the live histogram
  populations;
* the sampler unit: schema'd samples, per-interval deltas, source
  registry semantics, zero-thread identity when off;
* otpu_top: table/parsable rendering and stale-rank flagging from
  canned samples, plus THE acceptance run — ``otpu_top --json``
  attached to a live 3-rank tpurun job observes per-rank counter
  deltas advancing within two sampling intervals;
* flight recorder: dump triggers and payload shape in-process, plus
  the acceptance run — a chaos ``kill:rank=2,step=7`` job leaves a
  gathered bundle whose clock-aligned event order places the victim's
  last events before the survivors' recovery spans;
* otpu_analyze: last-arrival attribution and skew on synthetic
  timelines, plus the acceptance run — a rank-scoped chaos ``delay``
  makes the analyzer name the designed-slow rank as straggler for
  >= 90% of collectives.
"""
import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time
from pathlib import Path

import pytest

import launch

REPO = Path(__file__).resolve().parent.parent
WORKER = Path(__file__).resolve().parent / "telemetry_worker.py"


# ------------------------------------------------ trace snapshot/delta

def test_hist_snapshot_delta_never_resets():
    from ompi_tpu.runtime import trace

    trace.hist_reset("teletest")
    trace.hist_record("teletest", 4096, 1_000_000)
    snap1 = trace.hist_snapshot()
    trace.hist_record("teletest", 4096, 2_000_000)
    trace.hist_record("teletest", 64, 4_000_000)
    snap2 = trace.hist_snapshot()
    d = trace.hist_delta_stats(snap1, snap2)
    assert d["teletest"]["n"] == 2                 # both size bins merged
    assert d["teletest"]["sum_us"] == pytest.approx(6000.0)
    assert d["teletest"]["p99_us"] >= d["teletest"]["p50_us"] > 0
    # the LIVE population still holds all three records (no reset)
    key = ("teletest", int(4096).bit_length())
    assert trace.hist_snapshot()[key][0] == 2
    assert trace.hist_percentile("teletest", 0.5) > 0
    # an empty delta reports nothing (compact samples)
    assert trace.hist_delta_stats(snap2, trace.hist_snapshot()) == {}
    trace.hist_reset("teletest")


# --------------------------------------------------------- sampler unit

def _mk_world(monkeypatch, interval_ms=40):
    from ompi_tpu.base.var import registry
    from ompi_tpu.rte.coord import CoordServer
    from ompi_tpu.runtime import init as rt
    from ompi_tpu.runtime import telemetry  # noqa: F401  (registers var)

    srv = CoordServer(1)
    monkeypatch.setenv("OTPU_COORD", f"{srv.addr[0]}:{srv.addr[1]}")
    monkeypatch.setenv("OTPU_RANK", "0")
    monkeypatch.setenv("OTPU_NPROCS", "1")
    # API-source set: the var registered long before this test ran, so
    # an env value could not be (re)applied now
    registry.lookup("otpu_telemetry_interval_ms").set(interval_ms)
    rt.reset_for_testing()
    import ompi_tpu

    w = ompi_tpu.init()
    return srv, w, rt


def test_sampler_publishes_schemad_deltas(monkeypatch):
    import numpy as np

    from ompi_tpu.runtime import telemetry

    srv, w, rt = _mk_world(monkeypatch)
    try:
        assert telemetry.enabled and telemetry._sampler is not None
        x = np.ones(256, np.float32)
        deadline = time.monotonic() + 5.0
        first = None
        while time.monotonic() < deadline:
            w.allreduce(x)
            raw = srv.collect("otpu_telemetry")
            if 0 in raw:
                s = json.loads(raw[0])
                if first is None:
                    first = s
                elif s["seq"] > first["seq"]:
                    break
            time.sleep(0.02)
        else:
            pytest.fail("no advancing telemetry samples within 5s")
        # every key is schema-declared; the builtins are all present
        assert set(s) <= set(telemetry.SCHEMA)
        for key in ("seq", "t", "rank", "interval_ms", "spc",
                    "spc_delta", "hist"):
            assert key in s, key
        assert s["rank"] == 0 and s["interval_ms"] == 40
        # component sources rode along (tcp registers at btl init,
        # progress at module import)
        assert "progress" in s and "callbacks" in s["progress"]
    finally:
        from ompi_tpu.base.var import registry

        registry.lookup("otpu_telemetry_interval_ms").set(0)
        rt.reset_for_testing()
        srv.close()
        from ompi_tpu.runtime import telemetry as t2

        assert t2.enabled is False and t2._sampler is None


def test_register_source_schema_enforced():
    from ompi_tpu.runtime import telemetry

    with pytest.raises(ValueError):
        telemetry.register_source("mystery", dict)
    with pytest.raises(ValueError):
        telemetry.register_source("seq", dict)     # builtin keys too
    telemetry.register_source("serving", lambda: {"queued": 1})
    telemetry.unregister_source("serving")


def test_bound_method_sources_drop_with_their_owner():
    """A torn-down component must neither be kept alive by the source
    registry nor keep publishing frozen stats: bound-method sources are
    WeakMethod-held and silently drop when the owner is collected."""
    import gc

    from ompi_tpu.runtime import telemetry

    class Owner:
        def stats(self):
            return {"queued": 1}

    o = Owner()
    telemetry.register_source("serving", o.stats)
    s = telemetry.Sampler(0, 100)
    assert s._sample_once().get("serving") == {"queued": 1}
    del o
    gc.collect()
    assert "serving" not in s._sample_once()
    assert "serving" not in telemetry._sources


# ------------------------------------------------------- otpu_top unit

def _sample(rank, seq, interval_ms=100, **extra):
    s = {"seq": seq, "t": time.time(), "rank": rank,
         "interval_ms": interval_ms,
         "spc": {"allreduce": 100.0, "bytes_sent": 1e6},
         "spc_delta": {"allreduce": 10.0, "bytes_sent": 4096.0},
         "hist": {"allreduce": {"n": 10, "sum_us": 1000.0,
                                "p50_us": 90.0, "p99_us": 200.0}}}
    s.update(extra)
    return s


def test_otpu_top_render_and_stale_flag():
    from ompi_tpu.tools import otpu_top

    session = otpu_top.TopSession.__new__(otpu_top.TopSession)
    session.nprocs = 3
    session._last_seq = {}
    session._last_advance = {}
    samples = {0: _sample(0, 5, tcp={"outq_frags": 2, "outq_bytes": 99,
                                     "conns": 1}),
               1: _sample(1, 7, chaos={"delay": 3}),
               2: None}
    now = time.monotonic()
    session._last_advance = {0: now, 1: now - 10.0}
    session._last_seq = {0: 5, 1: 7}
    table = otpu_top.render_table(session, samples, "allreduce")
    assert "90/200us" in table                     # hist cell rendered
    assert "STALE" in table                        # rank 2 has no sample
    lines = table.splitlines()                     # [hdr, r0, r1, r2]
    assert lines[1].strip().endswith("ok")         # rank 0 fresh
    assert "STALE" in lines[2]                     # rank 1 seq stalled
    assert "STALE" in lines[3]                     # rank 2 no sample
    # rates come from the sample's own interval: 10 msgs / 100ms
    assert otpu_top._rate(samples[0], ("allreduce",)) == \
        pytest.approx(100.0)
    parsable = otpu_top.render_table(session, samples, "allreduce",
                                     parsable=True)
    assert parsable.splitlines()[1].startswith("1:7:")
    # a long-dead rank's frozen KV sample is stale on the FIRST poll
    # too: the sample's own wall-clock age flags it even when seq
    # tracking has nothing to compare against
    frozen = _sample(0, 9)
    frozen["t"] = time.time() - 60.0
    session._last_advance[0] = now          # seq rule says "fresh"
    assert session.stale(0, frozen) is True


# --------------------------------------------------- flight recorder unit

def test_flight_dump_payload_and_once_guard(monkeypatch, tmp_path):
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import flight, trace

    srv, w, rt = _mk_world(monkeypatch, interval_ms=0)
    registry.lookup("otpu_flight_dir").set(str(tmp_path / "crash"))
    try:
        trace._set_enabled(True)
        trace.span("step", "coll", trace.now())
        flight.reset_for_testing()
        from ompi_tpu.runtime import init as rt_mod

        flight.arm(rt_mod.get_rte())
        path = flight.dump("sanitize", detail="unit")
        assert path and os.path.exists(path)
        d = json.loads(Path(path).read_text())
        for key in ("rank", "reason", "trace_tail", "coord_rpcs",
                    "chaos_events", "spc", "clock_offset_us",
                    "failed_ranks"):
            assert key in d, key
        assert d["reason"] == "sanitize" and d["rank"] == 0
        assert any(e.get("name") == "step" for e in d["trace_tail"])
        assert d["coord_rpcs"], "recent-RPC ring is empty"
        # published into the coord KV for the launcher-side gather
        assert 0 in srv.collect("otpu_flight")
        # a RECOVERABLE sanitize dump may be superseded by a fatal
        # trigger (the process's actual death must not go undumped)...
        path2 = flight.dump("abort")
        assert path2 and json.loads(
            Path(path2).read_text())["reason"] == "abort"
        # ...but after a fatal dump the once-guard is final
        assert flight.dump("uncaught") is None
        assert flight.dump("sanitize") is None
    finally:
        flight.reset_for_testing()
        rt.reset_for_testing()
        srv.close()


def test_flight_dump_bundles_profile_snapshot(monkeypatch, tmp_path):
    """otpu-prof satellite: an armed stage-clock/profiler plane rides
    in the crash dump — rank<r>.json shows where host time was going
    (stage histograms + phase-sample counts); an unarmed plane dumps
    ``profile: null`` rather than fabricating numbers."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import flight, profile

    srv, w, rt = _mk_world(monkeypatch, interval_ms=0)
    registry.lookup("otpu_flight_dir").set(str(tmp_path / "crash3"))
    try:
        flight.reset_for_testing()
        profile.reset_for_testing()
        from ompi_tpu.runtime import init as rt_mod

        flight.arm(rt_mod.get_rte())
        # unarmed: the dump records the absence honestly
        path = flight.dump("sanitize", detail="no profile")
        assert json.loads(Path(path).read_text())["profile"] is None
        # armed: stage histograms + profiler phase counts ride along
        profile._set_enabled(True)
        profile.stage_span("send.pack", profile.now() - 5000)
        p = profile.HostProfiler(rank=0, interval_ms=5)
        with profile._lock:
            profile._profiler = p
        p.samples = 3
        p.phase_counts = {"idle": 2, "other": 1}
        p.total_obs = 3
        p.blocked_obs = 2
        path = flight.dump("abort", detail="with profile")
        prof = json.loads(Path(path).read_text())["profile"]
        assert prof["stages"]["send.pack"]["n"] == 1
        assert prof["profiler"]["phases"] == {"idle": 2, "other": 1}
        assert prof["profiler"]["samples"] == 3
    finally:
        profile.reset_for_testing()
        flight.reset_for_testing()
        rt.reset_for_testing()
        srv.close()


def test_sanitizer_fail_triggers_flight_dump(monkeypatch, tmp_path):
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import flight, sanitizer

    srv, w, rt = _mk_world(monkeypatch, interval_ms=0)
    registry.lookup("otpu_flight_dir").set(str(tmp_path / "crash2"))
    try:
        flight.reset_for_testing()
        from ompi_tpu.runtime import init as rt_mod

        flight.arm(rt_mod.get_rte())
        with pytest.raises(sanitizer.SanitizeError):
            sanitizer.fail("ownership invariant broken")
        # the dump runs on its own short-lived thread (fail() may fire
        # under a declared lock; the dump dials the coord service)
        dump = tmp_path / "crash2" / "rank0.json"
        deadline = time.monotonic() + 10.0
        while not dump.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert dump.exists(), "async sanitize dump never landed"
        assert json.loads(dump.read_text())["reason"] == "sanitize"
    finally:
        flight.reset_for_testing()
        rt.reset_for_testing()
        srv.close()


# ------------------------------------------------------- analyzer unit

def _synthetic_events(rounds=10, ranks=3, slow_rank=2, skew_us=500.0):
    events = []
    t = 0.0
    for _k in range(rounds):
        for r in range(ranks):
            start = t + (skew_us if r == slow_rank else r * 10.0)
            events.append({"ph": "X", "cat": "coll", "name": "allreduce",
                           "ts": start, "dur": 600.0,
                           "pid": r, "args": {"cid": 0, "nbytes": 4096}})
        t += 5000.0
    return sorted(events, key=lambda e: e["ts"])


def test_analyze_last_arrival_and_skew():
    from ompi_tpu.tools import otpu_analyze

    rep = otpu_analyze.analyze(_synthetic_events())
    assert rep["straggler"]["rank"] == 2
    assert rep["straggler"]["fraction"] == 1.0
    cell = rep["collectives"]["allreduce/cid0"]
    assert cell["rounds"] == 10 and cell["straggler_rank"] == 2
    assert cell["skew_us"]["max"] == pytest.approx(500.0)
    assert rep["skew_us"]["p50"] == pytest.approx(500.0)
    assert set(rep["exposed_comm"]) == {"0", "1", "2"}
    # diff: straggler movement is flagged
    rep2 = otpu_analyze.analyze(_synthetic_events(slow_rank=1))
    d = otpu_analyze.diff_reports(rep, rep2)
    assert d["straggler_changed"] is True
    assert d["straggler"] == [2, 1]


def test_analyze_loads_payload_files(tmp_path):
    """Per-rank payload form: events are clock-corrected by each
    payload's offset before attribution."""
    from ompi_tpu.tools import otpu_analyze

    events = _synthetic_events(rounds=4)
    for r in range(3):
        mine = [dict(e, ts=e["ts"] + 1000.0 * r)  # skewed local clocks
                for e in events if e["pid"] == r]
        (tmp_path / f"trace_rank{r}.json").write_text(json.dumps(
            {"traceEvents": mine,
             "metadata": {"rank": r,
                          "clock_offset_us": 1000.0 * r}}))
    rep = otpu_analyze.analyze(
        otpu_analyze.load_events([str(tmp_path)]))
    assert rep["straggler"]["rank"] == 2
    assert rep["rounds_total"] == 4


# ------------------------------------------------- live jobs (tpurun)

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_tpurun(n, port, mca, cmd, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    env.pop("OTPU_COORD", None)
    argv = [sys.executable, "-m", "ompi_tpu.tools.tpurun",
            "-n", str(n), "--coord-port", str(port), *extra]
    for k, v in mca:
        argv += ["--mca", k, v]
    argv += list(cmd)
    return subprocess.Popen(argv, cwd=REPO, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _wait_coord(port, timeout=30.0):
    from ompi_tpu.rte.coord import CoordClient

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            c = CoordClient(addr=("127.0.0.1", port), timeout=2.0,
                            retries=0)
            c._rpc(op="ping")
            return c
        except Exception:
            time.sleep(0.2)
    raise TimeoutError(f"coord service on :{port} never came up")


def test_otpu_top_attaches_to_live_job(tmp_path):
    """THE live-attach acceptance: otpu_top --json against a running
    3-rank job observes per-rank counter deltas advancing within two
    sampling intervals."""
    import contextlib
    import io

    from ompi_tpu.tools import otpu_top

    port = _free_port()
    env_extra = dict(os.environ)
    p = _launch_tpurun(
        3, port, [("otpu_telemetry_interval_ms", "150")],
        [sys.executable, str(WORKER)])
    try:
        c = _wait_coord(port)
        c.close()
        # poll every 0.15s: two sampler intervals = 300ms = 2 polls
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = otpu_top.main(["--coord", f"127.0.0.1:{port}",
                                "--json", "--interval", "0.15",
                                "--count", "20"])
        assert rc == 0
        polls = [json.loads(ln) for ln in
                 buf.getvalue().splitlines() if ln.strip()]
        assert polls and polls[0]["nprocs"] == 3
        # per-rank deltas advance within 2 sampling intervals: find,
        # for every rank, two polls <= 2 intervals apart whose seq
        # advanced and whose spc_delta shows traffic
        for rank in ("0", "1", "2"):
            seqs = [(poll["t"], poll["ranks"][rank]["seq"],
                     poll["ranks"][rank].get("spc_delta", {}))
                    for poll in polls
                    if poll["ranks"].get(rank)]
            assert seqs, f"rank {rank} never published"
            advanced = False
            for (t0, s0, _d0), (t1, s1, d1) in zip(seqs, seqs[1:]):
                if s1 > s0 and (t1 - t0) <= 0.45:
                    advanced = True
                    assert sum(d1.values()) > 0, (rank, d1)
                    break
            assert advanced, (rank, seqs)
    finally:
        out = p.communicate(timeout=120)[0]
    assert p.returncode == 0, out
    assert out.count("TELEMETRY WORKER DONE") == 3, out


_ELASTIC_FLIGHT_JOB = textwrap.dedent("""
    import sys
    import ompi_tpu
    from ompi_tpu.parallel.elastic import ElasticTrainer

    w = ompi_tpu.init()
    tr = ElasticTrainer(w, ckpt_dir=sys.argv[1], model_size=12,
                        global_batch=24, ckpt_every=5, respawn=False)
    tr.train(12)
    print("FLIGHTJOB DONE", w.rank, flush=True)
    ompi_tpu.finalize()
""")


def test_flight_bundle_on_chaos_kill(tmp_path):
    """THE flight-recorder acceptance: a chaos ``kill:rank=2,step=7``
    training run leaves a gathered bundle whose clock-aligned event
    order places the victim's last events before the survivors'
    revoke/shrink spans."""
    script = tmp_path / "job.py"
    script.write_text(_ELASTIC_FLIGHT_JOB)
    crash = tmp_path / "crash"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
           "--enable-recovery",
           "--mca", "otpu_chaos_spec", "kill:rank=2,step=7",
           "--mca", "otpu_trace_enable", "1",
           "--mca", "otpu_trace_dir", str(tmp_path / "trace"),
           "--mca", "otpu_flight_dir", str(crash),
           sys.executable, str(script), str(tmp_path / "ckpt")]
    r = launch.run(cmd, 300, env)
    out = r.stdout + r.stderr
    bundle_path = crash / "bundle.json"
    assert bundle_path.exists(), out
    bundle = json.loads(bundle_path.read_text())
    dumps = bundle["dumps"]
    assert dumps["2"]["reason"] == "chaos-kill", out
    survivors = [r_ for r_ in ("0", "1") if r_ in dumps]
    assert survivors, f"no survivor dumps: {sorted(dumps)}\n{out}"
    for s in survivors:
        assert dumps[s]["reason"] == "proc-failed"
        assert 2 in dumps[s]["failed_ranks"]
    # the coord's own view saw the failure event
    assert 2 in bundle["coord"]["failed"]
    assert any(e["name"] == "proc_failed"
               for e in bundle["coord"]["events"])
    # clock-aligned ordering: the victim's last event precedes the
    # survivors' recovery (shrink) spans on the merged tail
    merged = bundle["merged_tail"]
    victim_ts = [e["ts"] for e in merged if e["pid"] == 2]
    shrink_ts = [e["ts"] for e in merged
                 if e["pid"] != 2 and str(e.get("name", ""))
                 .startswith("elastic_shrink")]
    assert victim_ts, "victim trace tail missing from the bundle"
    assert shrink_ts, "survivor shrink spans missing from the bundle"
    assert max(victim_ts) < min(shrink_ts), (
        f"victim events [{max(victim_ts)}] not ordered before "
        f"survivor shrink [{min(shrink_ts)}]")


def test_analyzer_names_designed_straggler(tmp_path):
    """THE analyzer acceptance: a chaos ``delay`` scoped to one rank
    (``delay:ms=40,rank=2,site=step`` — the per-step pacing point) of a
    3-rank collective loop — otpu_analyze names rank 2 as the
    straggler for >= 90% of collectives."""
    tdir = tmp_path / "trace"
    env = dict(os.environ, JAX_PLATFORMS="cpu", TW_ITERS="25")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
           "--mca", "otpu_chaos_spec", "delay:ms=40,p=1,rank=2,site=step",
           "--mca", "otpu_trace_enable", "1",
           "--mca", "otpu_trace_dir", str(tdir),
           sys.executable, str(WORKER)]
    r = launch.run(cmd, 300, env)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    merged = tdir / "trace_merged.json"
    assert merged.exists(), out
    from ompi_tpu.tools import otpu_analyze

    rep = otpu_analyze.analyze(
        otpu_analyze.load_events([str(merged)]))
    assert rep["rounds_total"] >= 20, rep["rounds_total"]
    assert rep["straggler"]["rank"] == 2, rep["straggler"]
    assert rep["straggler"]["fraction"] >= 0.90, rep["straggler"]
    # the JSON report round-trips through the CLI --json/--diff path
    rep_path = tmp_path / "report.json"
    rc = otpu_analyze.main([str(merged), "--json", str(rep_path)])
    assert rc == 0
    again = json.loads(rep_path.read_text())
    assert again["straggler"]["rank"] == 2
    assert otpu_analyze.diff_reports(again, rep)[
        "straggler_changed"] is False
