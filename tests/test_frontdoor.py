"""ompi_tpu/serving/frontdoor — SLO-tiered admission, shedding,
preemption, and speculative decoding.

Coverage layers:

* token-bucket units: deterministic refill math against an injected
  clock, exact retry-after hints from the bucket deficit;
* door admission units (no comm): bounded-queue shed paths with the
  fd_retry_s hint, per-tenant rate-limit sheds, the one-class-per-
  tenant binding, forwarding order (interactive first, scheduler kept
  below the backlog watermark);
* preemption invariants over a REAL scheduler: an interactive-p99
  breach requeues RUNNING batch work (never dropped — same rids drain
  later), withdraws QUEUED batch work back behind the door, holds
  batch forwarding for fd_hold_ticks pumps, and bumps serve_preempt;
* speculative decoding: the draft/target toy pair's deterministic
  disagreement pattern, bit-exact output vs plain decode with PINNED
  accept/reject counters, then end-to-end through the colocated and
  prefill/decode staged modes (router re-verifies every token);
* THE overload soak (multiprocess, chaos-armed): MixedPoissonDriver
  above fleet capacity across both SLO classes through an armed front
  door — interactive p99 held within otpu_serving_slo_p99_ms, batch
  degrading predictably, every shed counted with its retry-after
  honored by the driver, zero crashes, zero dropped requests.
"""
import functools
import os
import threading
import weakref

import pytest

import ompi_tpu
from ompi_tpu.api.errors import MpiError
from ompi_tpu.base.var import registry
from ompi_tpu.runtime import spc
from ompi_tpu.serving.frontdoor import (SLO_BATCH, SLO_INTERACTIVE,
                                        FrontDoor, TokenBucket)
from ompi_tpu.serving.scheduler import ContinuousBatchScheduler

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(tpurun, timeout=300)


# ------------------------------------------------------- token bucket units

def test_token_bucket_deterministic_refill_math():
    b = TokenBucket(rate=10.0, burst=2.0, now=0.0)
    assert b.try_take(0.0) == 0.0          # burst tokens available
    assert b.try_take(0.0) == 0.0
    # empty: the hint is the EXACT deficit wait, (1 - tokens) / rate
    assert b.try_take(0.0) == pytest.approx(0.1, abs=1e-12)
    # half a token refilled after 0.05s: wait is the remaining half
    assert b.try_take(0.05) == pytest.approx(0.05, abs=1e-12)
    # after the full hint elapses the take succeeds
    assert b.try_take(0.05 + 0.1) == 0.0
    # refill caps at burst: a long idle gap does not bank extra tokens
    b2 = TokenBucket(rate=10.0, burst=2.0, now=0.0)
    for _ in range(2):
        assert b2.try_take(1000.0) == 0.0
    assert b2.try_take(1000.0) > 0.0

    with pytest.raises(MpiError):
        TokenBucket(rate=0.0, burst=1.0)


class _Pool:
    """Minimal router stand-in: the door only touches ``.sched``."""

    def __init__(self, **kw):
        kw.setdefault("max_batch", 8)
        kw.setdefault("max_batch_tokens", 65536)
        self.sched = ContinuousBatchScheduler(**kw)


def _door(pools=("m",), **kw):
    routers = {p: _Pool() for p in pools}
    kw.setdefault("queue_cap", 4)
    kw.setdefault("rate_rps", 0.0)
    kw.setdefault("backlog", 64)
    clock = kw.pop("clock", None) or (lambda: 0.0)
    fd = FrontDoor(routers, clock=clock, **kw)
    return fd, routers


# --------------------------------------------------------- admission units

def test_door_queue_full_sheds_with_retry_hint():
    spc.init()
    import ompi_tpu.serving.frontdoor as fd_mod

    fd, routers = _door(queue_cap=2, retry_s=0.25)
    try:
        shed0 = spc.read("serve_shed")
        assert fd.submit("t", "m", 8, 4).admitted
        assert fd.submit("t", "m", 8, 4).admitted
        dec = fd.submit("t", "m", 8, 4)
        assert not dec.admitted and dec.reason == "queue"
        assert dec.retry_after_s == pytest.approx(0.25)
        assert spc.read("serve_shed") == shed0 + 1
        st = fd.stats()
        assert st["shed"] == 1 and st["shed_by"] == {"t/interactive": 1}
        assert st["last_retry_ms"] == pytest.approx(250.0)
        # forwarding drains the door; capacity admits again
        fd.pump()
        assert fd.depth() == 0
        assert routers["m"].sched.depth() == 2
        assert fd.submit("t", "m", 8, 4).admitted
        fd.check_invariants()
        assert fd_mod.enabled is True and fd_mod._active is fd
    finally:
        fd.close()
    assert fd_mod.enabled is False and fd_mod._active is None


def test_door_rate_limit_sheds_with_exact_deficit():
    spc.init()
    now = [0.0]
    fd, _ = _door(rates={"t": (2.0, 1.0)}, queue_cap=16,
                  clock=lambda: now[0])
    try:
        assert fd.submit("t", "m", 8, 4).admitted      # the burst token
        dec = fd.submit("t", "m", 8, 4)
        assert not dec.admitted and dec.reason == "rate"
        assert dec.retry_after_s == pytest.approx(0.5)  # (1-0)/2 rps
        # honoring the hint admits deterministically
        now[0] = 0.5
        assert fd.submit("t", "m", 8, 4).admitted
        # an unlisted tenant uses the defaults (rate 0 = unlimited)
        for _ in range(3):
            assert fd.submit("free", "m", 8, 4).admitted
    finally:
        fd.close()


def test_door_binds_one_slo_class_per_tenant():
    fd, _ = _door()
    try:
        assert fd.submit("t", "m", 8, 4, slo=SLO_BATCH).admitted
        with pytest.raises(MpiError):
            fd.submit("t", "m", 8, 4, slo=SLO_INTERACTIVE)
        with pytest.raises(MpiError):
            fd.submit("u", "m", 8, 4, slo="gold")       # unknown class
        with pytest.raises(MpiError):
            fd.submit("u", "nope", 8, 4)                # unknown pool
    finally:
        fd.close()


def test_door_forwards_interactive_first_below_backlog():
    fd, routers = _door(queue_cap=16, backlog=3)
    sched = routers["m"].sched
    try:
        for _ in range(4):
            assert fd.submit("bat", "m", 8, 4, slo=SLO_BATCH).admitted
        for _ in range(4):
            assert fd.submit("int", "m", 8, 4,
                             slo=SLO_INTERACTIVE).admitted
        fd.pump()
        # the scheduler stays below the watermark and every forwarded
        # request is interactive — batch waits behind the door
        assert sched.depth() == 3
        assert all(r.slo == SLO_INTERACTIVE
                   for q in sched._tq.values() for r in q)
        assert fd.depth() == 5
        fd.check_invariants()
        # draining the scheduler lets the door top it back up (the
        # last interactive, then batch in arrival order)
        a, _ = sched.tick()
        for r in list(sched.running()):
            sched.mark_done(r)
        sched.tick()
        fd.pump()
        assert sched.depth() + len(sched.running()) >= 1
    finally:
        fd.close()


# ------------------------------------------------- preemption invariants

def test_preemption_requeues_batch_never_drops(monkeypatch):
    """An interactive-p99 breach must (a) requeue RUNNING batch work,
    (b) withdraw QUEUED batch work behind the door, (c) hold batch
    forwarding for fd_hold_ticks pumps, (d) count serve_preempt — and
    every preempted rid must drain later (never dropped)."""
    spc.init()
    registry.set("otpu_serving_slo_p99_ms", 10.0)
    try:
        fd, routers = _door(queue_cap=64, backlog=64, hold_ticks=3,
                            window=16)
        sched = routers["m"].sched
        try:
            bat = [fd.submit("bat", "m", 4, 2, slo=SLO_BATCH).request
                   for _ in range(6)]
            inter = [fd.submit("int", "m", 4, 2,
                               slo=SLO_INTERACTIVE).request
                     for _ in range(2)]
            fd.pump()                    # all 8 forwarded (backlog 64)
            assert sched.depth() == 8
            sched.tick()                 # admit up to max_batch (8)
            running = sched.running()
            assert len(running) == 8
            # breach: 16 interactive completions far over the target
            for _ in range(16):
                fd.observe("m", SLO_INTERACTIVE, 50.0)
            pre0 = spc.read("serve_preempt")
            fd.pump()
            # every RUNNING batch request went back to QUEUED and was
            # withdrawn behind the door with the queued batch work
            assert spc.read("serve_preempt") == pre0 + 6
            assert {r.rid for r in sched.running()} == \
                {r.rid for r in inter}
            assert sched.withdraw(SLO_BATCH) == []    # none left inside
            with fd._lock:
                door_bat = [r.rid for r in fd._q[("m", SLO_BATCH)]]
            assert door_bat == [r.rid for r in bat], \
                "preempted batch rids lost or reordered"
            fd.check_invariants()
            st = fd.stats()
            assert st["preempts"] == 6 and st["breaches"] == 1
            assert st["holds"] == {"m": 3}
            # the hold keeps batch out for hold_ticks pumps
            fd.pump()
            assert not [r for r in sched.running()
                        if r.slo == SLO_BATCH]
            fd.pump()
            fd.pump()                    # hold expired: batch returns
            assert [r.rid for q in sched._tq.values() for r in q] or \
                [r for r in sched.running() if r.slo == SLO_BATCH] or \
                fd.depth() == 0
            # drain everything: every admitted rid completes
            done = set()
            for _ in range(200):
                fd.pump()
                sched.tick()
                for r in list(sched.running()):
                    sched.mark_done(r)
                    done.add(r.rid)
                sched.tick()
                if not fd.depth() and not sched.depth() \
                        and not sched.running():
                    break
            assert done >= {r.rid for r in bat + inter}, \
                "a preempted request never drained"
            sched.check_invariants()
        finally:
            fd.close()
    finally:
        registry.set("otpu_serving_slo_p99_ms", 0.0)


def test_preemption_needs_a_real_window(monkeypatch):
    """No breach verdict from a thin window or without a target."""
    fd, routers = _door(window=16)
    try:
        # no target set: observe/pump never preempt
        for _ in range(32):
            fd.observe("m", SLO_INTERACTIVE, 1e6)
        fd.pump()
        assert fd.stats()["breaches"] == 0
    finally:
        fd.close()
    registry.set("otpu_serving_slo_p99_ms", 10.0)
    try:
        fd, routers = _door(window=16)
        try:
            for _ in range(8):           # under _MIN_WINDOW samples
                fd.observe("m", SLO_INTERACTIVE, 1e6)
            fd.pump()
            assert fd.stats()["breaches"] == 0
            # batch completions never feed the interactive window
            for _ in range(32):
                fd.observe("m", SLO_BATCH, 1e6)
            fd.pump()
            assert fd.stats()["breaches"] == 0
        finally:
            fd.close()
    finally:
        registry.set("otpu_serving_slo_p99_ms", 0.0)


# ------------------------------------------------- speculative decode units

def test_toy_draft_disagreement_pattern():
    from ompi_tpu.serving.worker import _VOCAB, toy_draft_token, toy_token

    for rid in (0, 7, 123):
        mismatches = [t for t in range(64)
                      if toy_draft_token(rid, t) != toy_token(rid, t)]
        assert mismatches == [t for t in range(64)
                              if (rid + t) % 8 == 5]
        for t in mismatches:
            assert toy_draft_token(rid, t) == \
                (toy_token(rid, t) + 1) % _VOCAB


def _bare_worker(spec_k, rid=7, elems=64):
    import numpy as np

    from ompi_tpu.serving.worker import ShardWorker

    w = ShardWorker.__new__(ShardWorker)
    w._kv = {rid: np.ones(elems, np.float32)}
    w.spec_k = spec_k
    return w


def test_speculative_decode_bit_exact_with_pinned_counters():
    from ompi_tpu.serving.worker import toy_token

    spc.init()
    plain = _bare_worker(0)._decode(7, 0, 16)
    assert plain == [toy_token(7, t) for t in range(16)]
    a0, r0 = spc.read("serve_spec_accepts"), spc.read("serve_spec_rejects")
    spec = _bare_worker(4)._decode(7, 0, 16)
    assert spec == plain, "speculative output must be bit-exact"
    # PINNED accept/reject ledger for (rid=7, 16 tokens, k=4): windows
    # [0..3]+bonus4, [5..8] rejected at 6, [7..10]+bonus11,
    # [12..15] rejected at 14, [15] — 12 accepted, 5 rejected
    assert spc.read("serve_spec_accepts") == a0 + 12
    assert spc.read("serve_spec_rejects") == r0 + 5
    # chunked exactly like the router's decode_chunk=4 stream
    w = _bare_worker(4)
    chunked = []
    for t0 in (0, 4, 8, 12):
        chunked.extend(w._decode(7, t0, 4))
    assert chunked == plain
    # the plain path never touches the draft counters
    a1, r1 = spc.read("serve_spec_accepts"), spc.read("serve_spec_rejects")
    _bare_worker(0)._decode(7, 0, 16)
    assert spc.read("serve_spec_accepts") == a1
    assert spc.read("serve_spec_rejects") == r1


# ----------------------------------------------------- in-process end-to-end

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


def test_speculative_colocated_end_to_end(world):
    """spec_k=4 through the real router/worker wire: the router
    re-verifies every token, so completing at all IS the bit-exactness
    proof — asserted explicitly anyway, plus live spec counters."""
    from ompi_tpu.serving import Router, ShardWorker
    from ompi_tpu.serving.worker import toy_token

    wk = ShardWorker(world.as_rank(1), router=0, spec_k=4)
    threads = _run_workers([wk])
    router = Router(world.as_rank(0), workers=[1], decode_chunk=4)
    a0 = spc.read("serve_spec_accepts")
    for i in range(4):
        router.submit(4 + i, 8, tenant="t")
    done = router.serve_until_drained(max_ticks=5000)
    router.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert len(done) == 4
    for req in done:
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]
    assert spc.read("serve_spec_accepts") > a0, \
        "speculative path never engaged"


def test_speculative_staged_end_to_end(world):
    """spec_k through the prefill/decode split: drafts ride the decode
    stage against streamed KV slabs, outputs stay the target stream."""
    from ompi_tpu.serving import Router, ShardWorker
    from ompi_tpu.serving.worker import toy_token

    pre = ShardWorker(world.as_rank(1), router=0, role="prefill",
                      peer=2, slots=4, kv_elems=32)
    dec = ShardWorker(world.as_rank(2), router=0, role="decode",
                      peer=1, slots=4, kv_elems=32, spec_k=4)
    threads = _run_workers([pre, dec])
    router = Router(world.as_rank(0), workers=[1, 2],
                    prefill_ranks=[1], decode_ranks=[2],
                    decode_chunk=4, kv_elems=32)
    a0 = spc.read("serve_spec_accepts")
    for i in range(4):
        router.submit(4 + i, 6, tenant="t")
    done = router.serve_until_drained(max_ticks=5000)
    router.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert len(done) == 4
    for req in done:
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]
    assert spc.read("serve_spec_accepts") > a0


def test_fleet_frontdoor_escalation_in_process(world):
    """Fleet + armed door end to end: overload sheds with retry-after
    (driver re-arrives them), every request still completes bit-exact,
    the report splits shed/retried/completed per tenant AND per SLO
    class, and the frontdoor telemetry source publishes."""
    from ompi_tpu.runtime import telemetry
    from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                                  PoolSpec, ShardWorker)
    from ompi_tpu.serving.worker import toy_token

    workers = [ShardWorker(world.as_rank(r), router=0) for r in (1, 2)]
    threads = _run_workers(workers)
    fleet = FleetController(
        world.as_rank(0),
        pools=[PoolSpec("m_a", [1, 2], max_batch=4,
                        max_batch_tokens=4096)],
        tenants={"int": 2, "bat": 1},
        frontdoor=dict(queue_cap=4, backlog=2, retry_s=0.02))
    assert fleet.frontdoor is not None
    drv = MixedPoissonDriver({
        "int": dict(model="m_a", rate_rps=800, n_requests=12,
                    prompt_lens=(4, 8), decode_lens=(2, 4),
                    slo="interactive"),
        "bat": dict(model="m_a", rate_rps=800, n_requests=10,
                    prompt_lens=(4, 8), decode_lens=(2, 4),
                    slo="batch"),
    }, seed=11)
    rep = drv.run(fleet, max_wall_s=90, check_invariants=True)
    door_stats = fleet.frontdoor.stats()
    fleet.shutdown()
    for t in threads:
        t.join(timeout=10)
    # zero dropped: every arrival completed (sheds re-arrived)
    assert rep["requests"] == 22
    for req in fleet.completed():
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]
    # the flood over a cap-4 door queue MUST have shed something, and
    # every shed re-arrived (retried) before completing
    assert rep["shed"] > 0 and rep["retried"] >= rep["shed"]
    for name in ("int", "bat"):
        tr = rep["tenants"][name]
        assert tr["retried"] >= tr["shed"]
    cls = rep["slo_classes"]
    assert cls["interactive"]["requests"] == 12
    assert cls["batch"]["requests"] == 10
    assert cls["interactive"]["shed"] + cls["batch"]["shed"] == \
        rep["shed"]
    # the door's telemetry source is registered and publishes
    assert door_stats["shed"] == rep["shed"]
    entry = telemetry._sources.get("frontdoor")
    assert entry is not None, "frontdoor never registered its source"
    fn = entry() if isinstance(entry, weakref.WeakMethod) else entry
    assert isinstance(fn(), dict)


# ------------------------------------------------------------- multiprocess

_OVERLOAD_SOAK = """
import sys

import ompi_tpu
from ompi_tpu.base.var import registry
from ompi_tpu.runtime import spc
from ompi_tpu.serving import (FleetController, MixedPoissonDriver,
                              ShardWorker)
from ompi_tpu.serving.worker import toy_token

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
    total = 28 + 36
    # zero crashes, zero dropped: every arrival (including every shed,
    # re-arrived after its retry-after) completed bit-exactly
    assert rep["requests"] == total, (rep["requests"], total)
    assert len({q.rid for q in fleet.completed()}) == total
    for q in fleet.completed():
        assert q.tokens == [toy_token(q.rid, i)
                            for i in range(q.max_new_tokens)], q
    # the chaos kill was absorbed by serve-through-failure
    assert rep["requeued"] > 0, "victim died, nothing requeued"
    # overload policy: the batch flood shed at the door (counted, with
    # retry-after honored — retried >= shed proves the driver honored
    # every hint), while unclassified nothing was shed
    assert rep["shed"] > 0, rep
    assert rep["retried"] >= rep["shed"], rep
    assert spc.read("serve_shed") == rep["shed"], \\
        (spc.read("serve_shed"), rep["shed"])
    cls = rep["slo_classes"]
    # interactive p99 held within the SLO target under overload;
    # batch degrades predictably (no better than interactive)
    assert cls["interactive"]["p99_exact_ms"] <= 800.0, cls
    assert cls["batch"]["p99_exact_ms"] >= \\
        cls["interactive"]["p99_exact_ms"], cls
    assert cls["batch"]["shed"] > 0, cls
    st = fleet.frontdoor.stats()
    assert st["shed"] == rep["shed"]
    fleet.shutdown()
    import json
    print("OVERLOAD OK " + json.dumps(
        {"shed": rep["shed"], "retried": rep["retried"],
         "preempts": st["preempts"],
         "int_p99": cls["interactive"]["p99_exact_ms"],
         "bat_p99": cls["batch"]["p99_exact_ms"],
         "requeued": rep["requeued"]}), flush=True)
else:
    if w.rank == 2:
        from ompi_tpu.ft import chaos
        chaos.install_spec("kill:rank=2,site=serve_work,count=1")
    ShardWorker(w, router=0).serve()
    print(f"WORKER {w.rank} DONE", flush=True)
"""


def test_frontdoor_overload_soak_chaos_armed(tmp_path):
    """THE acceptance scenario: sustained overload (arrivals above the
    pool's decode capacity) across both SLO classes through the armed
    front door, a worker chaos-killed mid-load — interactive p99 held,
    batch degraded predictably, sheds counted with honored retry-after,
    zero crashes, zero dropped requests."""
    script = tmp_path / "overload_soak.py"
    script.write_text(_OVERLOAD_SOAK)
    r = _tpurun(3, script,
                extra=("--enable-recovery", "--pool", "m_a:1,2"),
                timeout=300)
    assert "OVERLOAD OK" in r.stdout, r.stdout + r.stderr
