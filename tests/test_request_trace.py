"""otpu-req — per-request distributed tracing, tail-cohort attribution,
and SLO burn-rate accounting.

Coverage layers:

* pure units: request-key (``rid.hop``) round-trip through the real
  trace ring and Chrome export; SLO-accountant window math (burn rate,
  window pruning vs full-run totals, inert while no target is set);
* flight-recorder classification: a survivor whose recovery path dies
  on a secondary exception must dump ``proc-failed`` (the failed-set
  already observed wins), never ``uncaught`` — the fleet-soak flake;
* in-process engines (colocated + staged over ``as_rank`` views):
  every completed request decomposes into six stages that reconcile
  against its own e2e (stage-sum/e2e in (0, 1.25] — the single-stamp
  discipline pin) and renders a complete ``rid.hop`` arrow chain; the
  staged chain's middle hop rides the KV slab's Pready keys;
* multiprocess under tpurun: THE chaos-armed 2-pool/2-tenant soak with
  a designed-slow worker (``delay:ms=8,rank=2,site=serve_work``) —
  >=95% of completed requests decompose, the p99 tail cohort names a
  stage/tenant consistent with the slow worker, and the telemetry
  plane's burn rate agrees with the exact per-request sample within a
  declared band.
"""
import json
import os
import sys
import threading

import pytest

import ompi_tpu
from ompi_tpu.base.var import registry
from ompi_tpu.tools.otpu_analyze import (REQ_STAGES, _req_collect,
                                         requests_report)

import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ pure units

def test_request_key_round_trip():
    """A (rid, hop) flow key survives the real ring -> chrome export ->
    analyzer collect round trip: the export renders the dot-joined id
    at the TOP LEVEL of the flow event (Chrome's binding field), and
    ``_req_collect`` parses it back to the same (rid, hop) ints."""
    from ompi_tpu.runtime import trace

    registry.set("otpu_trace_enable", True)
    registry.set("otpu_trace_requests", True)
    trace.reset_for_testing()
    try:
        assert trace.requests_enabled is True
        t0 = trace.now()
        trace.flow_start("serve_req", (7, 0), t0)
        trace.flow_finish("serve_req", (7, 0))
        trace.flow_start("serve_req", (7, 2))
        trace.flow_finish("serve_req", (7, 2))
        trace.span("req_queue", "serve_req", t0,
                   args={"rid": 7, "tenant": "t", "pool": "p",
                         "worker": 1})
        evs = trace.chrome_events()
        halves = [e for e in evs if e.get("ph") in ("s", "f")
                  and e.get("name") == "serve_req"]
        assert [e["id"] for e in halves] == ["7.0", "7.0", "7.2", "7.2"]
        spans, flows = _req_collect(evs)
        assert set(flows) == {7} and set(flows[7]) == {0, 2}
        for hop in flows[7].values():
            assert set(hop) == {"s", "f"}
        assert set(spans) == {7} and "queue" in spans[7]
    finally:
        registry.set("otpu_trace_enable", False)
        registry.set("otpu_trace_requests", False)
        trace.reset_for_testing()


def test_slo_accountant_window_math():
    """Burn rate is (windowed breach fraction) / 1% budget; the rolling
    window prunes old completions while the full-run totals keep them;
    goodput counts only in-SLO completions."""
    from ompi_tpu.runtime import telemetry
    from ompi_tpu.serving import fleet  # noqa: F401  (registers target var)

    target = registry.lookup("otpu_serving_slo_p99_ms")
    window = registry.lookup("otpu_serving_slo_window_s")
    target.set(50.0)
    acct = telemetry.SloAccountant()
    try:
        for dur in (10.0, 20.0, 30.0):
            assert acct.observe("p", "ten", dur) is True
        assert acct.observe("p", "ten", 80.0) is False   # breach
        snap = acct.snapshot()
        cell = snap["pools"]["p"]["ten"]
        assert snap["target_ms"] == 50.0
        assert snap["budget"] == telemetry.SLO_BUDGET == 0.01
        assert cell["total"] == 4 and cell["breaches"] == 1
        # burn = (1/4) / 0.01 — 25x the error budget
        assert cell["burn"] == pytest.approx(25.0)
        assert cell["goodput_rps"] > 0
        assert cell["run_total"] == 4 and cell["run_breaches"] == 1
        # age the window out: everything prunes, run totals survive
        with acct._lock:
            dq = acct._win[("p", "ten")]
            aged = [(t - 3600.0, ok) for t, ok in dq]
            dq.clear()
            dq.extend(aged)
        cell = acct.snapshot()["pools"]["p"]["ten"]
        assert cell["total"] == 0 and cell["breaches"] == 0
        assert cell["burn"] == 0.0
        assert cell["run_total"] == 4 and cell["run_breaches"] == 1
    finally:
        target.set(0)
        window.set(60.0)


def test_slo_accountant_inert_without_target():
    """No target (the default) means NO state, no SPC traffic, and a
    None snapshot — the serving hot path pays one float compare."""
    from ompi_tpu.runtime import spc, telemetry
    from ompi_tpu.serving import fleet  # noqa: F401

    assert float(registry.lookup("otpu_serving_slo_p99_ms").value
                 or 0.0) == 0.0
    acct = telemetry.SloAccountant()
    before = spc.read("slo_goodput"), spc.read("slo_breaches")
    assert acct.observe("p", "ten", 1e9) is True      # even a "breach"
    assert acct.snapshot() is None
    assert not acct._win and not acct._totals
    assert (spc.read("slo_goodput"), spc.read("slo_breaches")) == before


# ----------------------------------------------- flight classification

def _hook_dumps(monkeypatch, failed):
    from ompi_tpu.ft import state as ft_state
    from ompi_tpu.runtime import flight

    dumps = []
    monkeypatch.setattr(flight, "dump",
                        lambda reason, detail="": dumps.append(
                            (reason, detail)))
    monkeypatch.setattr(ft_state, "failed_ranks", lambda: set(failed))
    monkeypatch.setattr(flight, "_orig_excepthook", lambda *a: None)
    flight._excepthook(ValueError, ValueError("boom"), None)
    return dumps


def test_flight_excepthook_prefers_proc_failed(monkeypatch):
    """The fleet-soak flake: a survivor observing dead peers dies on a
    secondary exception (its recovery-path coord RPC timed out) — the
    dump must classify by the failure already observed (proc-failed,
    failed set in the detail), with the exception riding along."""
    dumps = _hook_dumps(monkeypatch, failed={2})
    assert len(dumps) == 1
    reason, detail = dumps[0]
    assert reason == "proc-failed"
    assert detail.startswith("2 ") and "ValueError('boom')" in detail


def test_flight_excepthook_uncaught_when_no_failures(monkeypatch):
    dumps = _hook_dumps(monkeypatch, failed=())
    assert dumps == [("uncaught", "ValueError('boom')")]


# ------------------------------------------------- in-process engines

@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    from ompi_tpu.mca.part import part_framework

    part_framework().open()
    yield w
    rt.reset_for_testing()


@pytest.fixture()
def requests_on():
    from ompi_tpu.runtime import trace

    registry.set("otpu_trace_enable", True)
    registry.set("otpu_trace_requests", True)
    trace.reset_for_testing()
    assert trace.requests_enabled
    yield
    registry.set("otpu_trace_enable", False)
    registry.set("otpu_trace_requests", False)
    trace.reset_for_testing()


def _run_engine(world, stages, n_requests):
    from ompi_tpu.serving import ContinuousBatchScheduler, Router, \
        ShardWorker
    from ompi_tpu.serving.driver import PoissonDriver

    if stages:
        workers = [ShardWorker(world.as_rank(1), router=0,
                               role="prefill", peer=2, slots=8,
                               kv_elems=64),
                   ShardWorker(world.as_rank(2), router=0,
                               role="decode", peer=1, slots=8,
                               kv_elems=64, kv_partitions=16)]
    else:
        workers = [ShardWorker(world.as_rank(r), router=0)
                   for r in (1, 2)]
    threads = [threading.Thread(target=wk.serve, daemon=True)
               for wk in workers]
    for t in threads:
        t.start()
    r = Router(world.as_rank(0),
               scheduler=ContinuousBatchScheduler(max_batch=8,
                                                  max_batch_tokens=8192,
                                                  slots=8),
               workers=[1, 2], stages=stages, decode_chunk=3,
               kv_elems=64)
    rep = PoissonDriver(rate_rps=800, n_requests=n_requests,
                        seed=6).run(r, max_wall_s=90)
    r.shutdown()
    for t in threads:
        t.join(timeout=10)
    return rep


def test_colocated_requests_decompose(world, requests_on):
    """Satellite pin (single-stamp discipline): every completed request
    decomposes into the six stages, and the stage sum reconciles
    against the request's OWN e2e — in (0, 1.25] — which fails if any
    lifecycle point double-reads now() or a span pair crosses."""
    from ompi_tpu.runtime import spc, trace

    rep = _run_engine(world, stages=False, n_requests=16)
    report = requests_report(trace.chrome_events())
    assert report["requests_seen"] == rep["requests"] == 16
    assert report["decomposed"] == 16
    assert set(report["stage_median_us"]) == set(REQ_STAGES)
    band = report["stage_over_e2e"]
    assert 0.0 < band["min"] and band["max"] <= 1.25, band
    # colocated chains skip the kv hop (no slab stream) but still run
    # dispatch (0) -> completion (2) with both halves of each hop
    assert report["flows"]["chains_complete"] == 16
    assert spc.read("req_traced") >= 16


def test_staged_requests_full_chain(world, requests_on):
    """Disaggregated prefill/decode: the middle hop of the arrow chain
    rides the KV slab's per-sequence Pready partition key, so the
    sample chain has all three hops and the kv stage is non-trivial."""
    from ompi_tpu.runtime import trace

    rep = _run_engine(world, stages=True, n_requests=12)
    report = requests_report(trace.chrome_events())
    assert report["requests_seen"] == rep["requests"] == 12
    assert report["decomposed"] == 12
    band = report["stage_over_e2e"]
    assert 0.0 < band["min"] and band["max"] <= 1.25, band
    flows = report["flows"]
    assert flows["chains_complete"] == 12
    assert len(flows["sample"]["hops"]) == 3, flows["sample"]
    # every staged request streamed one KV block: the kv stage median
    # is a real measured wait, not a zero-width placeholder
    assert report["stage_median_us"]["kv"] > 0


# --------------------------------------------------- tpurun chaos soak

_SOAK = """
import json, sys
import ompi_tpu

w = ompi_tpu.init()
if w.rank == 0:
    from ompi_tpu.runtime import telemetry
    from ompi_tpu.serving import FleetController, MixedPoissonDriver
    fleet = FleetController(w, tenants={"ten_a": 2, "ten_b": 1})
    drv = MixedPoissonDriver({
        "ten_a": dict(model="m_a", rate_rps=300, n_requests=int(sys.argv[1]),
                      prompt_lens=(4, 16), decode_lens=(4, 10),
                      prefixes=2, prefix_len=16),
        "ten_b": dict(model="m_b", rate_rps=200, n_requests=int(sys.argv[2]),
                      prompt_lens=(4, 16), decode_lens=(4, 10),
                      prefixes=1, prefix_len=16),
    }, seed=7)
    rep = drv.run(fleet, max_wall_s=150)
    slo = telemetry.slo_snapshot()
    fleet.shutdown()
    print("REQSOAK " + json.dumps({"requests": rep["requests"],
                                   "slo": slo}), flush=True)
else:
    if w.rank == 2:
        from ompi_tpu.ft import chaos
        chaos.install_spec("delay:ms=8,rank=2,site=serve_work")
    from ompi_tpu.serving import ShardWorker
    ShardWorker(w, router=0).serve()
ompi_tpu.finalize()
"""

_SLO_MS = 50.0


def test_request_soak_chaos_tail_and_slo(tmp_path):
    """THE acceptance scenario: 2 pools / 2 tenants under mixed Poisson
    load with rank 2 (a pool-m_a worker) designed slow by 8ms per
    micro-batch.  Over the run's MERGED timeline: >=95% of completed
    requests decompose into six stages each reconciling against its
    own e2e; a complete router->worker->router arrow chain renders for
    at least one sampled request; the p99 tail cohort names a stage
    consistent with the load, and a tenant with a worker of its pool; and
    the telemetry plane's rolling burn rate agrees with the exact
    per-request breach fraction within the declared band (25% relative
    + 0.05 absolute on the breach fraction)."""
    from ompi_tpu.tools.otpu_analyze import load_events

    script = tmp_path / "req_soak.py"
    script.write_text(_SOAK)
    td = tmp_path / "traces"
    n_a, n_b = 24, 16
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "5",
         "--pool", "m_a:1,2", "--pool", "m_b:3,4",
         "--mca", "otpu_trace_enable", "1",
         "--mca", "otpu_trace_requests", "1",
         "--mca", "otpu_trace_dir", str(td),
         "--mca", "otpu_serving_slo_p99_ms", str(_SLO_MS),
         sys.executable, str(script), str(n_a), str(n_b)],
        300, env)
    line = next((ln for ln in r.stdout.splitlines() if "REQSOAK" in ln),
                None)
    assert r.returncode == 0 and line, r.stdout + r.stderr
    soak = json.loads(line.split("REQSOAK ", 1)[1])
    assert soak["requests"] == n_a + n_b

    report = requests_report(load_events([str(td)]), slo_ms=_SLO_MS)
    # >=95% decompose, each reconciling against its own e2e
    assert report["requests_seen"] >= 0.95 * (n_a + n_b)
    assert report["decomposed_fraction"] >= 0.95, report
    band = report["stage_over_e2e"]
    assert 0.0 < band["min"] and band["max"] <= 1.25, band
    # the merged timeline renders a complete per-request arrow chain
    flows = report["flows"]
    assert flows["chains_complete"] >= 1, flows
    sample = flows["sample"]
    assert sample["hops"][0].startswith("0:r0->") \
        and sample["hops"][-1].endswith("->r0"), sample
    # tail attribution: the time goes to the decode stage (or backs
    # the queue up).  40 requests make a p99 cohort of ONE, and which
    # request that is the host's scheduler decides, not the 8 ms delay
    # (a request spends 0.2-0.9 s queued and decoding): the
    # attribution must name a tenant and a worker of that tenant's
    # pool, not which
    tail = report["tail"]
    assert tail["cohort"] >= 1
    assert tail["dominant_stage"] in ("decode", "queue"), tail
    pools = {"ten_a": (1, 2), "ten_b": (3, 4)}
    assert tail["bounding_worker"] in pools[tail["hottest_tenant"]], tail
    # SLO agreement: telemetry's windowed accounting vs the analyzer's
    # exact per-request sample, within the declared band
    exact = report["slo_exact"]
    assert exact["target_ms"] == _SLO_MS
    slo = soak["slo"]
    assert slo and slo["target_ms"] == _SLO_MS
    tot = breaches = 0
    for tenants in slo["pools"].values():
        for cell in tenants.values():
            tot += cell["run_total"]
            breaches += cell["run_breaches"]
    assert tot >= 0.95 * (n_a + n_b)
    frac_t = breaches / max(1, tot)
    frac_e = exact["breach_fraction"]
    assert abs(frac_t - frac_e) <= 0.05 + 0.25 * frac_e, (
        f"telemetry breach fraction {frac_t:.4f} vs exact "
        f"{frac_e:.4f} — outside the declared band")
