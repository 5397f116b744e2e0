"""ompi_tpu/serving — the continuous-batching inference frontier.

Four layers of coverage:

* scheduler invariants (pure, no comm): strict-FIFO admission (no
  request starves), the batch never exceeds width/token/slot budgets,
  eviction without draining, requeue semantics;
* KV streaming (in-process loopback over mca/part): per-sequence
  ``Pready`` visibility, epoch exactness under MISMATCHED send/recv
  partition counts, epoch-desync loudness;
* the engine end to end in-process (router + worker threads over
  ``as_rank`` views): colocated and disaggregated stage modes, token
  bit-exactness, driver report sanity;
* multiprocess under tpurun: kill a worker mid-load and prove
  serve-through-failure (shrink to ``mpi://surviving``, re-shard, zero
  dropped requests), and (slow lane) autoscale via ``dpm.spawn`` +
  the ``mpi://job/<id>`` pset, plus the long Poisson soak.
"""
import functools
import os
import textwrap
import threading

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                        RequestState, ServeRequest)

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(tpurun, timeout=300)


# ---------------------------------------------------------------- scheduler

def test_scheduler_fifo_admission_no_starvation():
    """Admission is strictly arrival-ordered: with a stream of cheap
    requests behind one expensive head, nobody overtakes — and once
    capacity frees, the oldest queued request is always the next in."""
    s = ContinuousBatchScheduler(max_batch=2, max_batch_tokens=100)
    reqs = [s.submit(ServeRequest(10, 10)) for _ in range(8)]
    admitted_order = []
    for _ in range(40):
        admitted, _ = s.tick()
        admitted_order.extend(r.rid for r in admitted)
        s.check_invariants()
        for r in s.running():
            s.mark_done(r)
        if s.done_count() == len(reqs):
            break
    assert s.done_count() == len(reqs), "a request starved"
    assert admitted_order == [r.rid for r in reqs], \
        "admission broke arrival order"


def test_scheduler_budgets_hold_under_fuzz():
    rng = np.random.default_rng(7)
    s = ContinuousBatchScheduler(max_batch=4, max_batch_tokens=256,
                                 slots=6)
    live = []
    for step in range(300):
        if rng.random() < 0.5:
            s.submit(ServeRequest(int(rng.integers(1, 60)),
                                  int(rng.integers(1, 60))))
        admitted, evicted = s.tick()
        live.extend(admitted)
        s.check_invariants()
        assert len(s.running()) <= 4
        assert s.used_tokens() <= 256
        # finish a random running request now and then
        running = s.running()
        if running and rng.random() < 0.6:
            s.mark_done(running[int(rng.integers(len(running)))])
    # drain completely: every admitted request eventually evicts
    for _ in range(600):
        for r in s.running():
            s.mark_done(r)
        s.tick()
        s.check_invariants()
        if not s.running() and not s.depth():
            break
    assert not s.running() and not s.depth()


def test_scheduler_rejects_unadmittable_request():
    s = ContinuousBatchScheduler(max_batch=2, max_batch_tokens=64)
    with pytest.raises(MpiError) as ei:
        s.submit(ServeRequest(60, 10))      # cost 70 > 64: never fits
    assert ei.value.error_class is ErrorClass.ERR_ARG
    with pytest.raises(MpiError):
        ServeRequest(0, 4)                  # loud on degenerate lengths


def test_scheduler_eviction_without_drain():
    """Continuous batching: a short request admitted AFTER a long one
    completes and its freed capacity admits new work while the long
    request is still running — the batch never drains."""
    s = ContinuousBatchScheduler(max_batch=2, max_batch_tokens=1000)
    long_req = s.submit(ServeRequest(10, 100))
    short1 = s.submit(ServeRequest(10, 1))
    short2 = s.submit(ServeRequest(10, 1))
    s.tick()                          # admits long + short1 (width 2)
    assert short2.state is RequestState.QUEUED
    s.mark_done(short1)
    admitted, evicted = s.tick()      # short1 out, short2 in, long stays
    assert evicted == [short1] and admitted == [short2]
    assert long_req.state is RequestState.RUNNING
    assert long_req in s.running() and short2 in s.running()
    s.check_invariants()


def test_scheduler_requeue_skips_done_and_preserves_order():
    s = ContinuousBatchScheduler(max_batch=4, max_batch_tokens=1000)
    reqs = [s.submit(ServeRequest(5, 5)) for _ in range(4)]
    s.tick()
    s.mark_done(reqs[0])              # done-but-not-evicted at failure
    running = s.running()
    s.requeue(running)
    # the DONE request must NOT come back; the rest queue in arrival
    # order at the head with slots/token budget returned
    assert reqs[0].state is RequestState.DONE
    assert [r.rid for r in s._sq] == [r.rid for r in reqs[1:]]
    for r in reqs[1:]:
        assert r.state is RequestState.QUEUED and r.slot is None
        assert not r.prefilled
    s.tick()                          # evicts the done one, re-admits
    s.check_invariants()
    assert {r.rid for r in s.running()} == {r.rid for r in reqs[1:]}


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


# ---------------------------------------------------------------- kv stream

def test_kv_stream_pready_per_sequence_and_mismatched_counts(world):
    """One stage pair on loopback: receiver partitions = 2x sender
    slots.  A slot's block is visible (Parrived exact at sub-slot
    granularity) as soon as ITS Pready lands, before the epoch's tail
    flush; values are bit-exact across restarted epochs."""
    from ompi_tpu.serving.kv_stream import KvSlabReceiver, KvSlabSender
    from ompi_tpu.serving.worker import toy_kv
    from ompi_tpu.runtime.progress import progress

    a, b = world.as_rank(0), world.as_rank(1)
    snd = KvSlabSender(a, peer=1, slots=4, elems_per_slot=32, tag=77)
    rcv = KvSlabReceiver(b, peer=0, slots=4, elems_per_slot=32, tag=77,
                         partitions=8)
    for epoch in range(3):
        snd.begin_epoch(epoch)
        rcv.begin_epoch(epoch)
        snd.write_slot(2, toy_kv(epoch * 10 + 2, 32))
        snd.slot_ready(2)
        for _ in range(200):
            if rcv.slot_arrived(2):
                break
            progress()
        assert rcv.slot_arrived(2), "readied slot never arrived"
        np.testing.assert_array_equal(rcv.read_slot(2),
                                      toy_kv(epoch * 10 + 2, 32))
        snd.write_slot(0, toy_kv(epoch * 10, 32))
        snd.slot_ready(0)
        snd.finish_epoch(wait=True)    # aggregated tail flush
        rcv.finish_epoch()
        np.testing.assert_array_equal(rcv.read_slot(0),
                                      toy_kv(epoch * 10, 32))
    snd.free()
    rcv.free()


def test_kv_stream_epoch_desync_is_loud(world):
    from ompi_tpu.serving.kv_stream import KvSlabReceiver, KvSlabSender

    a, b = world.as_rank(2), world.as_rank(3)
    snd = KvSlabSender(a, peer=3, slots=2, elems_per_slot=8, tag=78)
    rcv = KvSlabReceiver(b, peer=2, slots=2, elems_per_slot=8, tag=78)
    with pytest.raises(MpiError):
        snd.begin_epoch(1)             # epochs are consecutive from 0
    snd.begin_epoch(0)
    rcv.begin_epoch(0)
    with pytest.raises(MpiError):
        rcv.read_slot(0)               # read before arrival is an error
    with pytest.raises(MpiError):
        KvSlabReceiver(b, peer=2, slots=2, elems_per_slot=8, tag=79,
                       partitions=3)   # partitions must tile slots
    snd.finish_epoch(wait=True)
    rcv.finish_epoch()
    snd.free()
    rcv.free()


# ------------------------------------------------------------- end to end

def test_colocated_engine_end_to_end(world):
    from ompi_tpu.serving import ContinuousBatchScheduler, Router, \
        ShardWorker
    from ompi_tpu.serving.driver import PoissonDriver
    from ompi_tpu.serving.worker import toy_token

    workers = [ShardWorker(world.as_rank(r), router=0) for r in (1, 2)]
    threads = _run_workers(workers)
    r = Router(world.as_rank(0),
               scheduler=ContinuousBatchScheduler(max_batch=4,
                                                  max_batch_tokens=4096),
               workers=[1, 2], decode_chunk=4)
    rep = PoissonDriver(rate_rps=800, n_requests=24,
                        seed=3).run(r, max_wall_s=90)
    r.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert rep["requests"] == 24
    assert rep["tokens"] > 0 and rep["tokens_per_s"] > 0
    # percentile report comes from the otpu-trace histogram; the exact
    # p99 over the driver's own samples must sit within the estimator's
    # one-log2-bin contract (factor-2 band) of it
    assert rep["p50_ms"] > 0 and rep["p99_ms"] > 0
    assert rep["p99_ms"] <= rep["p99_exact_ms"] * 2.0 + 1.0
    assert rep["p99_exact_ms"] <= rep["p99_ms"] * 2.0 + 1.0
    for req in r.completed():
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]


def test_stages_engine_kv_streams_end_to_end(world):
    """Disaggregated prefill/decode pair with a mismatched receiver
    partition count: every KV block is verified bit-exact by the decode
    stage (ShardWorker raises on corruption), every token by the
    router."""
    from ompi_tpu.serving import ContinuousBatchScheduler, Router, \
        ShardWorker
    from ompi_tpu.serving.driver import PoissonDriver
    from ompi_tpu.serving.worker import toy_token

    pre = ShardWorker(world.as_rank(1), router=0, role="prefill",
                      peer=2, slots=8, kv_elems=64)
    dec = ShardWorker(world.as_rank(2), router=0, role="decode",
                      peer=1, slots=8, kv_elems=64, kv_partitions=16)
    threads = _run_workers([pre, dec])
    r = Router(world.as_rank(0),
               scheduler=ContinuousBatchScheduler(max_batch=8,
                                                  max_batch_tokens=8192,
                                                  slots=8),
               workers=[1, 2], stages=True, decode_chunk=3, kv_elems=64)
    rep = PoissonDriver(rate_rps=800, n_requests=16,
                        seed=4).run(r, max_wall_s=90)
    r.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert rep["requests"] == 16
    from ompi_tpu.runtime import spc

    assert spc.read("serve_kv_epochs") > 0, "stages mode never streamed"
    for req in r.completed():
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]


def test_stages_two_pairs_staggered_epochs(world):
    """Two prefill/decode pairs with admissions landing on DIFFERENT
    ticks per pair: KV epochs are counted per pair, so a pair that sat
    out a round must not desync (the global-epoch bug the review
    caught)."""
    from ompi_tpu.serving import ContinuousBatchScheduler, Router, \
        ShardWorker
    from ompi_tpu.serving.worker import toy_token

    pre1 = ShardWorker(world.as_rank(1), router=0, role="prefill",
                       peer=3, slots=4, kv_elems=32)
    pre2 = ShardWorker(world.as_rank(2), router=0, role="prefill",
                       peer=4, slots=4, kv_elems=32)
    dec1 = ShardWorker(world.as_rank(3), router=0, role="decode",
                       peer=1, slots=4, kv_elems=32)
    dec2 = ShardWorker(world.as_rank(4), router=0, role="decode",
                       peer=2, slots=4, kv_elems=32)
    threads = _run_workers([pre1, pre2, dec1, dec2])
    r = Router(world.as_rank(0),
               scheduler=ContinuousBatchScheduler(max_batch=2,
                                                  max_batch_tokens=4096,
                                                  slots=4),
               workers=[1, 2, 3, 4], stages=True, decode_chunk=2,
               kv_elems=32)
    # staggered: narrow batch means later admissions land on whichever
    # pair freed up — pairs see fresh batches on different ticks
    for i in range(8):
        r.submit(4 + i, 2 + (i % 5))
    done = r.serve_until_drained(max_ticks=5000)
    r.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert len(done) == 8
    for req in done:
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]


def test_stages_odd_worker_serves_colocated(world):
    """An odd worker count in stages mode must not strand the leftover
    rank: it serves colocated and takes admissions."""
    from ompi_tpu.serving import ContinuousBatchScheduler, Router, \
        ShardWorker

    pre = ShardWorker(world.as_rank(1), router=0, role="prefill",
                      peer=2, slots=4, kv_elems=32)
    dec = ShardWorker(world.as_rank(2), router=0, role="decode",
                      peer=1, slots=4, kv_elems=32)
    extra = ShardWorker(world.as_rank(3), router=0)   # colocated
    threads = _run_workers([pre, dec, extra])
    r = Router(world.as_rank(0),
               scheduler=ContinuousBatchScheduler(max_batch=4,
                                                  max_batch_tokens=4096,
                                                  slots=4),
               workers=[1, 2, 3], stages=True, decode_chunk=2,
               kv_elems=32)
    for i in range(10):
        r.submit(6, 4)
    done = r.serve_until_drained(max_ticks=5000)
    r.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert len(done) == 10
    assert {q.worker for q in done} >= {2, 3}, \
        "the leftover rank never took work"


@pytest.mark.slow
def test_poisson_soak_invariants(world):
    """Long open-loop soak: heavy offered load, invariants checked on
    every tick, every request completes bit-exactly."""
    from ompi_tpu.serving import ContinuousBatchScheduler, Router, \
        ShardWorker
    from ompi_tpu.serving.driver import PoissonDriver
    from ompi_tpu.serving.worker import toy_token

    workers = [ShardWorker(world.as_rank(r), router=0) for r in (1, 2, 3)]
    threads = _run_workers(workers)
    sched = ContinuousBatchScheduler(max_batch=6, max_batch_tokens=4096)
    r = Router(world.as_rank(0), scheduler=sched, workers=[1, 2, 3],
               decode_chunk=2)
    drv = PoissonDriver(rate_rps=300, n_requests=200,
                        prompt_lens=(4, 96), decode_lens=(1, 48), seed=11)
    # drive manually so invariants run each tick
    import time as _time

    t0 = _time.perf_counter()
    while True:
        elapsed = _time.perf_counter() - t0
        assert elapsed < 300, "soak did not drain"
        for p, d in drv.due(elapsed):
            r.submit(p, d)
        r.tick()
        sched.check_invariants()
        if drv.exhausted and not sched.depth() and not sched.running():
            break
    r.shutdown()
    for t in threads:
        t.join(timeout=10)
    assert len(r.completed()) == 200
    for req in r.completed():
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]


# ------------------------------------------------------------- multiprocess

def test_serve_through_failure_zero_dropped(tmp_path):
    """The acceptance scenario: kill a worker mid-load under
    ``--enable-recovery``; the router revokes, shrinks to
    ``mpi://surviving``, re-shards its worker table, requeues the dead
    worker's in-flight requests, and EVERY admitted request completes
    bit-exactly."""
    script = tmp_path / "serve_fail.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        import ompi_tpu
        from ompi_tpu.serving import (ContinuousBatchScheduler, Router,
                                      ShardWorker)
        from ompi_tpu.serving.worker import toy_token

        w = ompi_tpu.init()
        if w.rank == 0:
            r = Router(w, scheduler=ContinuousBatchScheduler(
                           max_batch=6, max_batch_tokens=1 << 14),
                       decode_chunk=2)
            subs = [r.submit(8 + (i % 5), 6 + (i % 7)) for i in range(24)]
            done = r.serve_until_drained(max_ticks=20000)
            assert len(done) == 24, (len(done), 24)
            assert len({q.rid for q in done}) == 24, "duplicate finishes"
            for q in subs:
                assert q.tokens == [toy_token(q.rid, i)
                                    for i in range(q.max_new_tokens)], q
            assert r.lost_and_requeued > 0, "victim died, nothing requeued"
            assert len(r.workers) == 2, r.workers
            # the surviving pset the recovery rode is now advertised
            s = ompi_tpu.Session.init()
            assert "mpi://surviving" in s.psets()
            surv = s.group_from_pset("mpi://surviving")
            assert 2 not in surv.world_ranks
            s.finalize()
            r.shutdown()
            print(f"ROUTER OK requeued={r.lost_and_requeued}", flush=True)
        elif w.rank == 2:
            # chaos kill schedule replaces the old hand-rolled Victim
            # subclass: permit 2 micro-batches, die on the 3rd —
            # mid-load, results unsent (ShardWorker._on_work hosts the
            # serve_work kill point)
            from ompi_tpu.ft import chaos
            chaos.install_spec("kill:rank=2,site=serve_work,count=2")
            ShardWorker(w, router=0).serve()
        else:
            ShardWorker(w, router=0).serve()
            print(f"WORKER {w.rank} OK", flush=True)
    """))
    r = _tpurun(4, script, extra=("--enable-recovery",), timeout=300)
    assert "ROUTER OK" in r.stdout, r.stdout + r.stderr
    assert r.stdout.count("WORKER") == 2, r.stdout + r.stderr


@pytest.mark.slow
def test_autoscale_spawns_workers_via_job_pset(tmp_path):
    """Queue depth above the watermark spawns a fresh worker process
    (``dpm.spawn``), whose membership is verified against the dynamic
    ``mpi://job/<id>`` pset before merging into the serving comm."""
    script = tmp_path / "serve_scale.py"
    script.write_text(textwrap.dedent("""
        import sys
        import ompi_tpu
        from ompi_tpu.serving import (ContinuousBatchScheduler, Router,
                                      ShardWorker)
        from ompi_tpu.serving.worker import toy_token
        from ompi_tpu.runtime import spc

        w = ompi_tpu.init()
        if w.rank == 0:
            r = Router(w, scheduler=ContinuousBatchScheduler(
                           max_batch=2, max_batch_tokens=1 << 13),
                       decode_chunk=2, scale_watermark=3, scale_step=1,
                       scale_patience=2,
                       scale_argv=[sys.executable, "-m",
                                   "ompi_tpu.serving.worker"])
            subs = [r.submit(8, 8) for _ in range(12)]
            done = r.serve_until_drained(max_ticks=20000)
            assert len(done) == 12, len(done)
            for q in subs:
                assert q.tokens == [toy_token(q.rid, i)
                                    for i in range(q.max_new_tokens)]
            assert spc.read("serve_scaleups") >= 1, "never scaled"
            assert len(r.workers) == 2 and r.comm.size == 3
            r.shutdown()
            print(f"SCALE OK workers={r.workers}", flush=True)
        else:
            ShardWorker(w, router=0).serve()
            print("BASE WORKER OK", flush=True)
    """))
    r = _tpurun(2, script, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SCALE OK" in r.stdout, r.stdout + r.stderr


def test_tpurun_serving_role_flags(tmp_path):
    """--router-ranks/--worker-ranks publish the serving psets and
    roles() resolves placement from them (router NOT rank 0 here)."""
    script = tmp_path / "roles.py"
    script.write_text(textwrap.dedent("""
        import ompi_tpu
        from ompi_tpu import serving

        w = ompi_tpu.init()
        router, workers = serving.roles(w)
        assert router == 1, (router, workers)
        assert workers == [0, 2], (router, workers)
        print(f"ROLES OK {w.rank}", flush=True)
    """))
    r = _tpurun(3, script,
                extra=("--router-ranks", "1", "--worker-ranks", "0,2"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("ROLES OK") == 3
