"""ompi_tpu/serving/fleet — the multi-tenant serving platform.

Coverage layers:

* fair-share admission (pure scheduler): weighted round-robin across
  tenants with the checkable no-starvation invariant — a burst tenant
  cannot starve a light one, weights are respected, per-tenant FIFO
  holds;
* autoscaler policy units (fake fleet, no comm): PER-POOL cooldown
  (the regression: pool A absorbing its scale-up must not block pool
  B's needed spawn) and the per-pool max-workers cap;
* the fleet in-process end to end (router + worker threads over
  ``as_rank``): two pools, two tenants, prefix-cache hits actually
  skipping prefill, per-tenant percentile isolation, idle retirement
  into the reserve and a p99-SLO (telemetry-driven) re-enlist recorded
  in the otpu-trace ring;
* multiprocess under tpurun: THE chaos-armed soak — sustained mixed
  Poisson load across 2 models/tenants with a worker chaos-killed
  mid-load, zero dropped requests, prefix hit-rate > 0 with a
  measurable prefill-count delta, and at least one autoscale decision
  driven by a telemetry sample (p99 from the coord-KV sample, NOT
  queue depth) spawning a real replacement via ``dpm.spawn`` into the
  pool pset (bounded tier-1 run; the full-length version rides the
  ``slow`` lane).
"""
import functools
import os
import subprocess
import sys
import textwrap
import threading

import pytest

import ompi_tpu
from ompi_tpu.api.errors import MpiError
from ompi_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                        ServeRequest)

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(tpurun, timeout=300)


# ----------------------------------------------------- fair-share admission

def test_fair_share_burst_cannot_starve():
    """One tenant floods 50 requests; the other trickles 5.  The light
    tenant's requests must land within the first WRR cycles (never
    starve), the weights must shape the admitted mix, and the
    scheduler's own cross-tenant invariant must hold every tick."""
    s = ContinuousBatchScheduler(max_batch=2, max_batch_tokens=10000,
                                 tenants={"burst": 3, "light": 1})
    for _ in range(50):
        s.submit(ServeRequest(5, 5, tenant="burst"))
    for _ in range(5):
        s.submit(ServeRequest(5, 5, tenant="light"))
    admitted = []
    for _ in range(200):
        a, _e = s.tick()
        admitted.extend(r.tenant for r in a)
        s.check_invariants()
        for r in s.running():
            s.mark_done(r)
        if not s.depth() and not s.running():
            break
    assert not s.depth() and not s.running()
    assert "light" in admitted[:8], admitted[:8]
    head = admitted[:20]
    # 3:1 weights while both backlogged (light exhausts after 5)
    assert head.count("burst") == 15 and head.count("light") == 5, head


def test_fair_share_per_tenant_fifo_and_dynamic_tenant():
    s = ContinuousBatchScheduler(max_batch=4, max_batch_tokens=10000,
                                 tenants={"a": 1})
    r1 = s.submit(ServeRequest(4, 4, tenant="a"))
    # a tenant first seen at submit time joins with weight 1
    r2 = s.submit(ServeRequest(4, 4, tenant="newcomer"))
    r3 = s.submit(ServeRequest(4, 4, tenant="a"))
    a, _ = s.tick()
    s.check_invariants()
    assert {r.rid for r in a} == {r1.rid, r2.rid, r3.rid}
    assert s.tenant_depths() == {"": 0, "a": 0, "newcomer": 0}
    # per-tenant FIFO: within tenant a, r1 admitted before r3
    ia = [r.rid for r in a if r.tenant == "a"]
    assert ia == [r1.rid, r3.rid]


def test_fair_share_invariant_trips_on_violation():
    """The invariant checker must actually detect starvation — feed a
    poisoned admission log and expect the assertion."""
    s = ContinuousBatchScheduler(max_batch=2, max_batch_tokens=10000,
                                 tenants={"a": 1, "b": 1})
    with s._slock:
        for _ in range(10):     # "a" admitted 10x while b backlogged
            s._admit_log.append(("a", ("b",)))
    with pytest.raises(AssertionError, match="passed over"):
        s.check_invariants()


def test_tenant_weight_must_be_positive():
    with pytest.raises(MpiError):
        ContinuousBatchScheduler(tenants={"a": 0})


# ------------------------------------------------- autoscaler policy units

class _FakeSched:
    def __init__(self):
        self.queued = 0

    def stats(self):
        return {"queued": self.queued, "running": 0}

    def depth(self):
        return self.queued


class _FakeRouter:
    def __init__(self, workers):
        self.workers = list(workers)
        self.sched = _FakeSched()
        self.registry = None


class _FakeRte:
    client = None


class _FakeComm:
    rte = _FakeRte()


class _FakeFleet:
    """Just enough fleet for FleetAutoscaler: routers, capacity hooks,
    decision log."""

    def __init__(self):
        self.routers = {"a": _FakeRouter([1]), "b": _FakeRouter([2])}
        self.comm = _FakeComm()
        self.enlisted = []
        self.retired = []
        self.decisions = []

    def enlist(self, pool):
        self.enlisted.append(pool)
        self.routers[pool].workers.append(99)
        return 99

    def spawn_into(self, pool, n=1):
        return []

    def retire(self, pool):
        self.retired.append(pool)
        w = self.routers[pool].workers.pop()
        return w

    def note_decision(self, d):
        self.decisions.append(d)


def test_autoscale_cooldown_is_per_pool():
    """THE regression: with pool A cooling after its scale-up, pool
    B's burst must still trigger B's spawn — a single global cooldown
    timer would block it."""
    from ompi_tpu.serving.fleet import FleetAutoscaler

    fleet = _FakeFleet()
    a = FleetAutoscaler(fleet, depth_high=0, patience=1, cooldown=10,
                        poll_ticks=1, slo_p99_ms=0.0,
                        watch_stale=False, idle_patience=10**9)
    fleet.routers["a"].sched.queued = 5          # only A is deep
    a.step()
    assert fleet.enlisted == ["a"]
    assert a._cooling["a"] == 10, "A must now cool down"
    fleet.routers["a"].sched.queued = 0
    fleet.routers["b"].sched.queued = 5          # B gets deep LATER
    a.step()
    assert fleet.enlisted == ["a", "b"], \
        "pool A's cooldown blocked pool B's needed scale-up"
    # and A, still cooling, does not double-scale even if deep again
    fleet.routers["a"].sched.queued = 9
    a.step()
    assert fleet.enlisted == ["a", "b"]


def test_autoscale_max_workers_cap_is_per_pool():
    from ompi_tpu.serving.fleet import FleetAutoscaler

    fleet = _FakeFleet()
    a = FleetAutoscaler(fleet, depth_high=0, patience=1, cooldown=0,
                        poll_ticks=1, slo_p99_ms=0.0,
                        watch_stale=False, idle_patience=10**9,
                        max_workers={"a": 1, "b": 3})
    fleet.routers["a"].sched.queued = 5
    fleet.routers["b"].sched.queued = 5
    a.step()
    assert fleet.enlisted == ["b"], \
        "pool A is at its cap; only B may scale"


def test_autoscale_idle_retirement():
    from ompi_tpu.serving.fleet import FleetAutoscaler

    fleet = _FakeFleet()
    fleet.routers["a"].workers = [1, 5]
    a = FleetAutoscaler(fleet, depth_high=None, poll_ticks=1,
                        slo_p99_ms=0.0, watch_stale=False,
                        idle_patience=3, cooldown=4, min_workers=1)
    for _ in range(3):
        a.step()
    assert fleet.retired == ["a"], "idle pool A should drain one rank"
    # pool B sits at min_workers: never retired below the floor
    for _ in range(10):
        a.step()
    assert fleet.retired.count("b") == 0
    assert a.stats()["downs"] == 1


# ------------------------------------------------------------ in-process env

@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    from ompi_tpu.mca.part import part_framework

    part_framework().open()
    yield w
    rt.reset_for_testing()


def _run_workers(workers):
    threads = [threading.Thread(target=wk.serve, daemon=True)
               for wk in workers]
    for t in threads:
        t.start()
    return threads


def test_fleet_two_pools_two_tenants_end_to_end(world):
    """Two model pools + two weighted tenants under mixed Poisson
    load: every token bit-exact, per-tenant percentiles isolated,
    prefix-cache hits measurably skipping prefill."""
    from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                                  PoolSpec, ShardWorker)
    from ompi_tpu.serving.worker import toy_token

    workers = [ShardWorker(world.as_rank(r), router=0)
               for r in (1, 2, 3, 4)]
    threads = _run_workers(workers)
    fleet = FleetController(world.as_rank(0), pools=[
        PoolSpec("m_a", [1, 2], max_batch=4, max_batch_tokens=4096),
        PoolSpec("m_b", [3, 4], max_batch=4, max_batch_tokens=4096),
    ], tenants={"ten_a": 2, "ten_b": 1})
    drv = MixedPoissonDriver({
        "ten_a": dict(model="m_a", rate_rps=600, n_requests=16,
                      prompt_lens=(4, 24), decode_lens=(2, 8),
                      prefixes=2, prefix_len=32),
        "ten_b": dict(model="m_b", rate_rps=400, n_requests=12,
                      prompt_lens=(4, 24), decode_lens=(2, 8),
                      prefixes=1, prefix_len=16),
    }, seed=7)
    rep = drv.run(fleet, max_wall_s=90, check_invariants=True)
    fleet.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert rep["requests"] == 28
    for req in fleet.completed():
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]
    # per-tenant report: separate populations, sane estimator bands
    for name in ("ten_a", "ten_b"):
        tr = rep["tenants"][name]
        assert tr["requests"] == (16 if name == "ten_a" else 12)
        assert tr["p50_ms"] > 0 and tr["p99_ms"] > 0
        assert tr["p99_ms"] <= tr["p99_exact_ms"] * 2.0 + 1.0
        assert tr["p99_exact_ms"] <= tr["p99_ms"] * 2.0 + 1.0
    # prefix-cache evidence: hits happened AND skipped prefill passes
    assert rep["prefix_hits"] > 0
    assert rep["prefills"] + rep["prefix_hits"] >= 28
    assert rep["prefills"] < 28, \
        "every request prefilled — the cache skipped nothing"
    st = fleet.stats()
    assert st["pools"]["m_a"]["prefix"]["hits"] > 0
    assert st["pools"]["m_a"]["workers"] == 2


def test_fleet_per_tenant_hist_reset_isolation(world):
    """Per-tenant percentile populations must not merge across runs:
    poison the tenant family with an absurd sample, re-run, and the
    reported p99 must reflect only the fresh run."""
    from ompi_tpu.runtime import trace
    from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                                  PoolSpec, ShardWorker)
    from ompi_tpu.serving.router import TENANT_HIST_PREFIX

    workers = [ShardWorker(world.as_rank(r), router=0) for r in (1,)]
    threads = _run_workers(workers)
    fleet = FleetController(world.as_rank(0),
                            pools=[PoolSpec("m_x", [1])],
                            tenants={"t0": 1})
    # poison: one 100-second sample in t0's family
    trace.hist_record(TENANT_HIST_PREFIX + "t0", 32, int(100e9))
    drv = MixedPoissonDriver({
        "t0": dict(model="m_x", rate_rps=500, n_requests=8,
                   prompt_lens=(4, 8), decode_lens=(2, 4))}, seed=2)
    rep = drv.run(fleet, max_wall_s=60)
    fleet.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert rep["tenants"]["t0"]["p99_ms"] < 50_000, \
        "poisoned pre-run sample leaked into the tenant's percentiles"


def test_fleet_autoscaler_telemetry_decision_in_trace(world):
    """Idle retirement parks a rank in the reserve; a p99-SLO breach —
    read from a telemetry SAMPLE, not queue depth — re-enlists it, and
    the decision lands in the otpu-trace ring naming the signal."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import trace
    from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                                  PoolSpec, ShardWorker)

    workers = [ShardWorker(world.as_rank(r), router=0)
               for r in (1, 2, 3)]
    threads = _run_workers(workers)
    fleet = FleetController(
        world.as_rank(0),
        pools=[PoolSpec("m_a", [1, 2], max_batch=4,
                        max_batch_tokens=4096),
               PoolSpec("m_b", [3], max_batch=4,
                        max_batch_tokens=4096)],
        tenants={"ten_a": 1},
        autoscale=dict(poll_ticks=2, idle_patience=3, cooldown=4,
                       slo_p99_ms=0.0001, min_workers=1,
                       watch_stale=False))
    # idle ticks: pool A drains one rank into the reserve
    for _ in range(30):
        fleet.tick()
    assert fleet.stats()["reserve"] >= 1
    assert len(fleet.routers["m_a"].workers) == 1
    # loaded run under an absurd SLO: the p99 signal must re-enlist
    was = trace.enabled
    if not was:
        registry.set("otpu_trace_enable", True)
    try:
        drv = MixedPoissonDriver({
            "ten_a": dict(model="m_a", rate_rps=2000, n_requests=30,
                          prompt_lens=(8, 16), decode_lens=(4, 8))},
            seed=1)
        drv.run(fleet, max_wall_s=60)
        ups = [d for d in fleet.stats()["decisions"]
               if d["dir"] == "up"]
        assert any(d["signal"] == "p99" for d in ups), ups
        ring = [e[6] for e in trace._ring if e is not None
                and e[1] == "fleet_scale"]
        assert any(d.get("signal") == "p99" and d.get("dir") == "up"
                   for d in ring), \
            "no telemetry-driven decision in the trace ring"
        assert len(fleet.routers["m_a"].workers) == 2, \
            "the reserve rank was not re-enlisted"
    finally:
        fleet.shutdown()
        for t in threads:
            t.join(timeout=10)
        if not was:
            registry.set("otpu_trace_enable", False)


def test_fleet_rejects_bad_pools(world):
    from ompi_tpu.serving import FleetController, PoolSpec

    with pytest.raises(MpiError, match="shares workers"):
        FleetController(world.as_rank(0),
                        pools=[PoolSpec("a", [1, 2]),
                               PoolSpec("b", [2, 3])])
    with pytest.raises(MpiError, match="at least one pool"):
        FleetController(world.as_rank(0), pools=[])
    with pytest.raises(MpiError, match="at least one worker"):
        PoolSpec("a", [])
    with pytest.raises(MpiError, match="given together"):
        PoolSpec("a", [1, 2], prefill=[1])
    fleet = FleetController(world.as_rank(0),
                            pools=[PoolSpec("a", [1])])
    with pytest.raises(MpiError, match="no serving pool"):
        fleet.submit("t", "nope", prompt_len=4, max_new_tokens=2)


def test_fleet_stages_pool_sized_independently(world):
    """A disaggregated pool with 1 prefill feeding 2 decode ranks:
    the prefill rank holds one slab pairing per decode peer and every
    token still verifies."""
    from ompi_tpu.serving import (FleetController, PoolSpec,
                                  ShardWorker)
    from ompi_tpu.serving.worker import toy_token

    pre = ShardWorker(world.as_rank(1), router=0, role="prefill",
                      peer=[2, 3], slots=4, kv_elems=32)
    dec1 = ShardWorker(world.as_rank(2), router=0, role="decode",
                       peer=1, slots=4, kv_elems=32)
    dec2 = ShardWorker(world.as_rank(3), router=0, role="decode",
                       peer=1, slots=4, kv_elems=32)
    threads = _run_workers([pre, dec1, dec2])
    fleet = FleetController(world.as_rank(0), pools=[
        PoolSpec("m_s", [1, 2, 3], prefill=[1], decode=[2, 3],
                 max_batch=2, max_batch_tokens=4096, slots=4,
                 decode_chunk=2, kv_elems=32)])
    for i in range(8):
        fleet.submit("", "m_s", prompt_len=4 + i, max_new_tokens=3)
    done = fleet.serve_until_drained(max_ticks=5000)
    fleet.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert len(done) == 8
    assert {q.worker for q in done} == {2, 3}, \
        "both decode ranks must take work"
    for req in done:
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]


def test_retire_is_stage_aware(world):
    """Scale-down must never wedge a stage pool: colocated extras
    leave first, and the last prefill / last decode rank is
    untouchable even when the pool still has several workers."""
    from ompi_tpu.serving import FleetController, PoolSpec

    fleet = FleetController(world.as_rank(0), pools=[
        PoolSpec("m_s", [1, 2, 3], prefill=[1], decode=[2])])
    assert fleet.retire("m_s") == 3, "the colocated extra goes first"
    assert fleet.retire("m_s") is None, \
        "the last prefill/decode ranks must be protected"
    assert fleet.routers["m_s"].workers == [1, 2]
    # a wider decode pool may shrink — newest decode rank first
    fleet2 = FleetController(world.as_rank(0), pools=[
        PoolSpec("m_t", [4, 5, 6], prefill=[4], decode=[5, 6])])
    assert fleet2.retire("m_t") == 6
    assert fleet2.retire("m_t") is None


def test_mixed_driver_drives_bare_router(world):
    """MixedPoissonDriver's documented bare-Router mode: same driver,
    no fleet controller."""
    from ompi_tpu.serving import (MixedPoissonDriver, Router,
                                  ShardWorker)
    from ompi_tpu.serving.worker import toy_token

    wk = ShardWorker(world.as_rank(7), router=0)
    threads = _run_workers([wk])
    router = Router(world.as_rank(0), workers=[7], decode_chunk=4)
    rep = MixedPoissonDriver({
        "solo": dict(model="", rate_rps=500, n_requests=6,
                     prompt_lens=(4, 8), decode_lens=(2, 4))},
        seed=9).run(router, max_wall_s=60)
    router.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert rep["requests"] == 6
    assert rep["tenants"]["solo"]["requests"] == 6
    for req in router.completed():
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]


# ------------------------------------------------------------- multiprocess

_SOAK = """
import sys

import ompi_tpu
from ompi_tpu.runtime import trace
from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                              ShardWorker)
from ompi_tpu.serving.worker import toy_token

N_A, N_B = int(sys.argv[1]), int(sys.argv[2])
w = ompi_tpu.init()
if w.rank == 0:
    # pools resolve from the tpurun --pool psets (no explicit specs)
    fleet = FleetController(
        w, tenants={"ten_a": 2, "ten_b": 1},
        spawn_argv=[sys.executable, "-m", "ompi_tpu.serving.worker"],
        autoscale=dict(poll_ticks=2, depth_high=None, cooldown=25,
                       slo_p99_ms=2.0, max_workers=3,
                       idle_patience=10**9))
    assert fleet.pool_workers() == {"m_a": [1, 2], "m_b": [3, 4]}, \\
        fleet.pool_workers()
    drv = MixedPoissonDriver({
        "ten_a": dict(model="m_a", rate_rps=300, n_requests=N_A,
                      prompt_lens=(4, 16), decode_lens=(4, 10),
                      prefixes=2, prefix_len=32),
        "ten_b": dict(model="m_b", rate_rps=200, n_requests=N_B,
                      prompt_lens=(4, 16), decode_lens=(4, 10),
                      prefixes=1, prefix_len=16),
    }, seed=3)
    rep = drv.run(fleet, max_wall_s=150)
    total = N_A + N_B
    # zero dropped: every admitted request completed, bit-exactly
    assert rep["requests"] == total, (rep["requests"], total)
    assert len({q.rid for q in fleet.completed()}) == total
    for q in fleet.completed():
        assert q.tokens == [toy_token(q.rid, i)
                            for i in range(q.max_new_tokens)], q
    assert rep["requeued"] > 0, "victim died, nothing requeued"
    # prefix cache: hits happened and measurably skipped prefills
    assert rep["prefix_hits"] > 0, rep
    assert rep["prefills"] < total, rep
    # at least one autoscale decision came from a TELEMETRY sample
    # (p99 / stale_rank), not queue depth — and reached the trace ring
    ring = [e[6] for e in trace._ring if e is not None
            and e[1] == "fleet_scale"]
    assert any(d.get("dir") == "up"
               and d.get("signal") in ("p99", "stale_rank")
               for d in ring), ring
    st = fleet.stats()
    assert st["autoscale"]["ups"] >= 1
    fleet.shutdown()
    import json
    print("SOAK OK " + json.dumps(
        {"requeued": rep["requeued"], "hits": rep["prefix_hits"],
         "prefills": rep["prefills"],
         "ups": st["autoscale"]["ups"]}), flush=True)
else:
    if w.rank == 2:
        from ompi_tpu.ft import chaos
        chaos.install_spec("kill:rank=2,site=serve_work,count=2")
    ShardWorker(w, router=0).serve()
    print(f"WORKER {w.rank} DONE", flush=True)
"""


def _soak(tmp_path, n_a, n_b, timeout):
    script = tmp_path / "fleet_soak.py"
    script.write_text(_SOAK)
    return _tpurun(
        5, [sys.executable, str(script), str(n_a), str(n_b)],
        extra=("--enable-recovery", "--pool", "m_a:1,2",
               "--pool", "m_b:3,4",
               "--mca", "otpu_telemetry_interval_ms", "50"),
        timeout=timeout)


def test_fleet_chaos_soak_bounded(tmp_path):
    """THE acceptance scenario (bounded): mixed two-tenant Poisson
    load over two --pool pools while a worker is chaos-killed
    mid-load; zero dropped requests, prefix hit-rate > 0 with a
    prefill-count delta, and a telemetry-driven (p99) scale decision
    spawning a replacement via dpm.spawn into the pool."""
    r = _soak(tmp_path, 24, 16, timeout=300)
    assert "SOAK OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_fleet_chaos_soak_full(tmp_path):
    """The full-length soak: same invariants, 4x the load."""
    r = _soak(tmp_path, 96, 64, timeout=480)
    assert "SOAK OK" in r.stdout, r.stdout + r.stderr


def test_tpurun_pool_psets_resolve(tmp_path):
    """--pool publishes mpi://serving/pool/<model> and
    pool_specs_from_psets resolves the tables from it."""
    script = tmp_path / "pools.py"
    script.write_text(textwrap.dedent("""
        import ompi_tpu
        from ompi_tpu.serving import pool_specs_from_psets

        w = ompi_tpu.init()
        specs = {s.name: s.workers for s in pool_specs_from_psets(w)}
        assert specs == {"left": [1], "right": [2, 3]}, specs
        print(f"POOLS OK {w.rank}", flush=True)
    """))
    r = _tpurun(4, script, extra=("--pool", "left:1",
                                  "--pool", "right:2-3"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("POOLS OK") == 4


def test_otpu_info_serving_surface():
    """otpu_info --serving lists the registry-enumerated serving vars
    (and works under --parsable, matching --telemetry/--profile)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.otpu_info", "--serving"],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr
    for var in ("otpu_serving_prefix_block", "otpu_serving_slo_p99_ms",
                "otpu_serving_scale_cooldown", "otpu_serving_slo_window_s",
                "otpu_trace_requests"):
        assert var in out.stdout, var
    # the otpu-req surfaces: SLO telemetry key and the registry-
    # enumerated request/SLO SPC counters
    assert "serving telemetry key slo" in out.stdout
    for ctr in ("req_traced", "req_stages", "slo_goodput",
                "slo_breaches"):
        assert f"serving counter {ctr}" in out.stdout, ctr
    # the front-door surfaces: admission vars, the speculative window,
    # the frontdoor telemetry key, and the shed/preempt/spec counters
    for var in ("otpu_serving_fd_queue_cap", "otpu_serving_fd_rate_rps",
                "otpu_serving_fd_hold_ticks", "otpu_serving_spec_k"):
        assert var in out.stdout, var
    assert "serving telemetry key frontdoor" in out.stdout
    for ctr in ("serve_shed", "serve_preempt", "serve_spec_accepts",
                "serve_spec_rejects"):
        assert f"serving counter {ctr}" in out.stdout, ctr
    par = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.otpu_info", "--serving",
         "--parsable"],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert par.returncode == 0
    assert any(ln.startswith("serving var otpu_serving_prefix_block:")
               for ln in par.stdout.splitlines()), par.stdout

# ---------------------------------------- coord recovery budget (the flake)

def test_coord_recovery_budget_resolution():
    """The documented fleet-soak flake fix: RPCs inside
    ``recovery_scope()`` take the recovery retry/timeout budget
    (``otpu_coord_recovery_retry_max`` / ``_rpc_timeout``), scopes
    nest, the budget never SHORTENS a raised steady-state ladder, and
    everything reverts when the outermost scope exits."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.rte import coord

    c = coord.CoordClient.__new__(coord.CoordClient)
    c._retry_max = 2
    c._recovery_depth = 0
    c._rpc_timeout = 1.5
    assert c._effective_retry_max() == 2
    assert c._effective_rpc_timeout() == 1.5
    with c.recovery_scope():
        assert c._effective_retry_max() == 24       # the var default
        with c.recovery_scope():                    # scopes nest
            assert c._effective_retry_max() == 24
        assert c._effective_retry_max() == 24       # outer still open
        # recovery never shortens a caller-raised steady-state ladder
        c._retry_max = 100
        assert c._effective_retry_max() == 100
        # the rpc timeout inherits steady state until the var is set
        assert c._effective_rpc_timeout() == 1.5
        registry.set("otpu_coord_recovery_rpc_timeout", 9.0)
        try:
            assert c._effective_rpc_timeout() == 9.0
        finally:
            registry.set("otpu_coord_recovery_rpc_timeout", 0.0)
    assert c._recovery_depth == 0
    assert c._effective_retry_max() == 100
    assert c._effective_rpc_timeout() == 1.5


def test_coord_recovery_scope_survives_reconnect_burst():
    """Behavioral pin against a hostile server: with the steady-state
    ladder (retries=1) a burst of connection kills exhausts the budget
    and raises; the SAME burst inside ``recovery_scope()`` is absorbed
    by the recovery budget and the RPC completes."""
    import socket

    from ompi_tpu.rte import coord

    kills = {"n": 0}
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    addr = srv.getsockname()

    def _conn(conn):
        try:
            while True:
                req = coord._recv_frame(conn)
                if kills["n"] > 0:
                    # swallow the request, reset the connection — the
                    # client sees a ConnectionError and walks its
                    # reconnect ladder
                    kills["n"] -= 1
                    conn.close()
                    return
                coord._send_frame(conn, {"ok": True, "value": None,
                                         "_rid": req.get("_rid")})
        except (ConnectionError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _accept():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            threading.Thread(target=_conn, args=(conn,),
                             daemon=True).start()

    threading.Thread(target=_accept, daemon=True).start()
    try:
        c = coord.CoordClient(addr=addr, retries=1)
        c.put(0, "warm", 1)                  # the happy path works
        kills["n"] = 3
        with pytest.raises((ConnectionError, OSError)):
            c.put(0, "k", 2)                 # steady-state ladder: 1
        kills["n"] = 3                       # retry, then exhausted
        with c.recovery_scope():
            c.put(0, "k", 3)                 # recovery budget: 24
        assert kills["n"] == 0, "recovery path never hit the server"
        c.put(0, "after", 4)                 # steady state restored
        assert c._recovery_depth == 0
    finally:
        srv.close()


def test_agreement_wraps_coord_in_recovery_scope():
    """agree_kv's coord traffic rides the client's recovery scope when
    one exists — and degrades to a no-op context for bare test fakes
    (the shrink path must not demand the full client surface)."""
    import contextlib

    from ompi_tpu.ft import agreement

    class _Client:
        entered = 0

        @contextlib.contextmanager
        def recovery_scope(self):
            _Client.entered += 1
            try:
                yield self
            finally:
                _Client.entered -= 1

    cl = _Client()
    with agreement._recovery_scope(cl):
        assert _Client.entered == 1
    assert _Client.entered == 0
    # a fake without the method gets nullcontext, not AttributeError
    with agreement._recovery_scope(object()):
        pass
