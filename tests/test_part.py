"""mca/part — MPI-4 partitioned communication (SURVEY §1/§2 part/persist
analog): Psend_init/Precv_init with Pready/Pready_range/Pready_list and
Parrived, aggregation onto fewer wire messages, mismatched send/recv
partition counts, mixed Startall, loud error paths, a seeded Pready-order
fuzz vs a numpy reference and the partitioned device collective
(pcoll)."""
import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.api.request import start_all

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(tpurun, timeout=300)


@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    from ompi_tpu.mca.part import part_framework

    part_framework().open()   # registers otpu_part_persist_* vars
    yield w
    rt.reset_for_testing()


@pytest.fixture
def min_partitions(world):
    """Set-and-restore handle on the aggregation var."""
    from ompi_tpu.base.var import registry

    var = registry.lookup("otpu_part_persist_min_partitions")
    old = var.value

    def setter(v):
        var.set(v)

    yield setter
    var.set(old)


def test_partitioned_pingpong_single_process(world):
    a, b = world.as_rank(0), world.as_rank(1)
    x = np.arange(24.0)
    y = np.zeros(24)
    s = a.psend_init(x, 4, dest=1, tag=11)
    r = b.precv_init(y, 4, source=0, tag=11)
    for epoch in range(3):                       # restartable
        x[:] = np.arange(24.0) * (epoch + 1)
        start_all([s, r])
        assert not r.complete_flag
        s.pready_range(0, 3)
        s.wait()
        r.wait()
        np.testing.assert_array_equal(y, x)
        assert all(r.parrived(p) for p in range(4))


def test_out_of_order_and_interleaved_pready(world):
    a, b = world.as_rank(2), world.as_rank(3)
    x = np.arange(32.0)
    y = np.zeros(32)
    s = a.psend_init(x, 8, dest=3, tag=12)
    r = b.precv_init(y, 8, source=2, tag=12)
    start_all([s, r])
    # interleave: ready a few, observe arrival, ready the rest reversed
    s.pready_list([5, 1])
    assert r.parrived(5) and r.parrived(1)
    assert not r.parrived(0)
    psize = 32 // 8
    np.testing.assert_array_equal(y[5 * psize:6 * psize],
                                  x[5 * psize:6 * psize])
    for p in (7, 6, 4, 3, 2, 0):
        s.pready(p)
    s.wait()
    r.wait()
    np.testing.assert_array_equal(y, x)


def test_mismatched_partition_counts(world):
    a, b = world.as_rank(0), world.as_rank(1)
    x = np.arange(48.0)
    # send 4 partitions / recv 3, then send 2 / recv 8 (same bytes)
    for sp, rp in ((4, 3), (2, 8), (6, 1)):
        y = np.zeros(48)
        s = a.psend_init(x, sp, dest=1, tag=13)
        r = b.precv_init(y, rp, source=0, tag=13)
        start_all([s, r])
        for p in np.random.RandomState(sp).permutation(sp):
            s.pready(int(p))
        s.wait()
        r.wait()
        np.testing.assert_array_equal(y, x)
        assert all(r.parrived(p) for p in range(rp))


def test_aggregation_reduces_wire_messages(world, min_partitions):
    from ompi_tpu.runtime import spc

    a, b = world.as_rank(4), world.as_rank(5)
    x = np.arange(64.0)
    y = np.zeros(64)
    min_partitions(4)
    s = a.psend_init(x, 8, dest=5, tag=14)
    r = b.precv_init(y, 8, source=4, tag=14)
    m0 = spc.read("part_msgs")
    start_all([s, r])
    for p in range(8):          # in-order: one run of 4 + forced rest
        s.pready(p)
    s.wait()
    r.wait()
    np.testing.assert_array_equal(y, x)
    assert spc.read("part_msgs") - m0 == 2
    # and Parrived still tracks under aggregated framing
    min_partitions(8)
    start_all([s, r])
    s.pready_range(0, 6)
    assert not r.parrived(0)    # whole run held below the threshold
    s.pready(7)                 # final pready force-flushes one message
    s.wait()
    r.wait()
    assert all(r.parrived(p) for p in range(8))


def test_startall_mixed_classic_and_partitioned(world):
    a, b = world.as_rank(6), world.as_rank(7)
    xp = np.arange(16.0)
    xc = np.full(4, 7.0)
    yp = np.zeros(16)
    yc = np.zeros(4)
    sp = a.psend_init(xp, 4, dest=7, tag=15)
    sc = a.send_init(xc, dest=7, tag=16)
    rp = b.precv_init(yp, 2, source=6, tag=15)
    rc = b.recv_init(yc, source=6, tag=16)
    start_all([sp, sc, rp, rc])
    sp.pready_list(range(4))
    from ompi_tpu.api.request import waitall

    waitall([sp, sc, rp, rc])
    np.testing.assert_array_equal(yp, xp)
    np.testing.assert_array_equal(yc, xc)


def test_error_paths(world):
    a, b = world.as_rank(0), world.as_rank(1)
    x = np.arange(8.0)
    y = np.zeros(8)
    s = a.psend_init(x, 4, dest=1, tag=17)
    r = b.precv_init(y, 4, source=0, tag=17)
    # Pready before start (inactive)
    with pytest.raises(MpiError) as exc:
        s.pready(0)
    assert exc.value.error_class is ErrorClass.ERR_REQUEST
    # Parrived before the first start
    with pytest.raises(MpiError) as exc:
        r.parrived(0)
    assert exc.value.error_class is ErrorClass.ERR_REQUEST
    start_all([s, r])
    # out-of-range partition indices, both sides
    with pytest.raises(MpiError) as exc:
        s.pready(4)
    assert exc.value.error_class is ErrorClass.ERR_ARG
    with pytest.raises(MpiError):
        s.pready(-1)
    with pytest.raises(MpiError) as exc:
        r.parrived(99)
    assert exc.value.error_class is ErrorClass.ERR_ARG
    # double-Pready of the same partition
    s.pready(2)
    with pytest.raises(MpiError) as exc:
        s.pready(2)
    assert exc.value.error_class is ErrorClass.ERR_ARG
    # Parrived on the send side / Pready on the recv side
    with pytest.raises(MpiError) as exc:
        s.parrived(0)
    assert exc.value.error_class is ErrorClass.ERR_REQUEST
    with pytest.raises(MpiError) as exc:
        r.pready(0)
    assert exc.value.error_class is ErrorClass.ERR_REQUEST
    # Pready/Parrived on a non-partitioned request
    req = a.send_init(x, dest=1, tag=18)
    with pytest.raises(MpiError):
        req.pready(0)
    with pytest.raises(MpiError):
        req.parrived(0)
    # drain the open epoch so no posted traffic dangles
    s.pready_list([0, 1, 3])
    s.wait()
    r.wait()
    # init-time validation: wildcards, bad counts, bad buffers
    from ompi_tpu.api.status import ANY_SOURCE, ANY_TAG

    with pytest.raises(MpiError):
        b.precv_init(y, 4, source=ANY_SOURCE, tag=1)
    with pytest.raises(MpiError):
        a.psend_init(x, 4, dest=1, tag=ANY_TAG)
    with pytest.raises(MpiError):
        a.psend_init(x, 3, dest=1, tag=1)      # 8 % 3 != 0
    with pytest.raises(MpiError):
        a.psend_init(x, 0, dest=1, tag=1)
    with pytest.raises(MpiError):
        a.psend_init([1.0, 2.0], 2, dest=1, tag=1)   # not an ndarray
    ro = np.arange(8.0)
    ro.setflags(write=False)
    with pytest.raises(MpiError):
        b.precv_init(ro, 4, source=0, tag=1)


def test_fuzz_random_pready_orders(world, min_partitions):
    """Seeded fuzz: random partition counts (mismatched send/recv),
    random Pready orders, random aggregation thresholds — every epoch
    validated against the numpy reference copy."""
    rng = np.random.RandomState(1234)
    a, b = world.as_rank(1), world.as_rank(2)
    for trial in range(12):
        sp = int(rng.randint(1, 9))
        rp = int(rng.randint(1, 9))
        unit = int(rng.randint(1, 5))
        count = sp * rp * unit
        x = rng.normal(size=count)
        y = np.zeros(count)
        min_partitions(int(rng.randint(1, 5)))
        s = a.psend_init(x, sp, dest=2, tag=20 + trial)
        r = b.precv_init(y, rp, source=1, tag=20 + trial)
        for _ in range(int(rng.randint(1, 3))):
            start_all([s, r])
            order = rng.permutation(sp)
            for p in order[:sp // 2]:
                s.pready(int(p))
            # poll some random Parrived mid-stream (must not disturb)
            for p in rng.randint(0, rp, size=3):
                r.parrived(int(p))
            for p in order[sp // 2:]:
                s.pready(int(p))
            s.wait()
            r.wait()
            np.testing.assert_array_equal(y, x)
            assert all(r.parrived(p) for p in range(rp))


def test_proc_null_partitioned(world):
    from ompi_tpu.api.status import PROC_NULL

    a = world.as_rank(0)
    x = np.arange(8.0)
    s = a.psend_init(x, 4, dest=PROC_NULL, tag=1)
    r = a.precv_init(np.zeros(8), 4, source=PROC_NULL, tag=1)
    start_all([s, r])
    r.wait()                      # completes immediately
    s.pready_range(0, 3)
    s.wait()
    assert r.parrived(0)


def test_partitioned_pingpong_multiprocess(tmp_path):
    script = tmp_path / "part_pp.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu

        w = ompi_tpu.init()
        parts = 8
        x = np.arange(64.0) + 100 * w.rank
        y = np.zeros(64)
        peer = 1 - w.rank
        if w.rank == 0:
            s = w.psend_init(x, parts, dest=1, tag=3)
            r = w.precv_init(y, 4, source=1, tag=4)   # mismatched counts
        else:
            r = w.precv_init(y, 4, source=0, tag=3)
            s = w.psend_init(x, parts, dest=0, tag=4)
        for epoch in range(2):
            x[:] = np.arange(64.0) + 100 * w.rank + epoch
            if w.rank == 0:
                s.start()
                for p in (5, 0, 7, 2, 1, 6, 3, 4):    # out of order
                    s.pready(p)
                s.wait()
                r.start(); r.wait()
            else:
                r.start(); r.wait()
                s.start()
                for p in range(parts):
                    s.pready(p)
                s.wait()
            want = np.arange(64.0) + 100 * (1 - w.rank) + epoch
            assert np.array_equal(y, want), (w.rank, epoch, y[:4])
            assert all(r.parrived(p) for p in range(4))
        print(f"PART OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script)
    assert r.stdout.count("PART OK") == 2, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr


def test_partitioned_aggregated_multiprocess(tmp_path):
    """Aggregation var honored across processes; Parrived tracks under
    aggregated framing (several app partitions per wire message)."""
    script = tmp_path / "part_agg.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.runtime import spc

        w = ompi_tpu.init()
        x = np.arange(256.0)
        y = np.zeros(256)
        if w.rank == 0:
            s = w.psend_init(x, 16, dest=1, tag=2)
            m0 = spc.read("part_msgs")
            s.start()
            for p in range(16):
                s.pready(p)
            s.wait()
            sent = spc.read("part_msgs") - m0
            assert sent == 4, sent     # 16 partitions / min 4 -> 4 msgs
        else:
            r = w.precv_init(y, 8, source=0, tag=2)
            r.start()
            r.wait()
            assert np.array_equal(y, x)
            assert all(r.parrived(p) for p in range(8))
        print(f"AGG OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script,
                extra=("--mca", "part_persist_min_partitions", "4"))
    assert r.stdout.count("AGG OK") == 2, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr


def test_pallreduce_init_device_pcoll(world):
    """Partitioned persistent allreduce: each bucket bound once, released
    by Pready in production order, result per bucket."""
    n = world.size
    buckets = [np.full((n, 4), float(i + 1), np.float32)
               for i in range(3)]
    req = world.pallreduce_init(buckets)
    req.start()
    for i in (2, 1, 0):                     # late bucket first
        req.pready(i)
        # dispatch is async: Parrived flips once the device result lands
        for _ in range(2000):
            if req.parrived(i):
                break
        assert req.parrived(i)
    req.wait()
    for i in range(3):
        np.testing.assert_allclose(np.asarray(req.result[i]),
                                   (i + 1) * n)
    # restart with fresh data (device arrays are immutable)
    req.start([b * 2 for b in buckets])
    with pytest.raises(MpiError):
        req.pready(3)                       # out of range
    req.pready_range(0, 2)
    with pytest.raises(MpiError):
        req.pready(1)                       # double release
    req.wait()
    np.testing.assert_allclose(np.asarray(req.result[2]), 6 * n)


def test_pallreduce_failed_dispatch_does_not_wedge(world):
    """A pready whose dispatch raises (rebind with a bucket mismatching
    the bound template) must NOT release the bucket: the same error
    surfaces again on retry (not 'already released'), and the request
    stays freeable/restartable instead of wedging wait() forever."""
    n = world.size
    good = [np.ones((n, 4), np.float32)]
    req = world.pallreduce_init(good)
    # len ok, but the leading axis is not divisible by the mesh size,
    # so the bound program's sharded dispatch raises
    req.start([np.ones((n + 1, 4), np.float32)])
    with pytest.raises(Exception) as first:
        req.pready(0)
    assert "already released" not in str(first.value)
    with pytest.raises(Exception) as again:      # rollback: same error
        req.pready(0)
    assert "already released" not in str(again.value)
    req.free()
    req.start(good)
    req.pready(np.int64(0))                      # numpy index accepted
    req.wait()
    np.testing.assert_allclose(np.asarray(req.result[0]), float(n))


def test_pallreduce_matches_plain_allreduce(world):
    n = world.size
    rng = np.random.RandomState(7)
    buckets = [rng.normal(size=(n, 8)).astype(np.float32)
               for i in range(4)]
    req = world.pallreduce_init(buckets)
    req.start()
    req.pready_list(range(4))
    req.wait()
    for b, got in zip(buckets, req.result):
        # f32 reduction order differs between the bound device program
        # and the plain path — equal within a few ulp, not bitwise
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(world.allreduce(b)),
                                   rtol=1e-5)


def _xla(world):
    return world.c_coll["allreduce_array"].__self__


@pytest.fixture
def pgroup_bar(world):
    """Set-and-restore handle on the planner's bar (the module's
    ``pgroup_min_bytes``, ``coll/xla`` ``PGROUP_MIN_BYTES`` by default)."""
    m = _xla(world)
    old = m.pgroup_min_bytes
    yield lambda v: setattr(m, "pgroup_min_bytes", v)
    m.pgroup_min_bytes = old


#: float32 elements one rank holds of each bucket (4 bytes each)
_BUCKET_ELEMS = {
    "equal": [16] * 8,
    "ragged": [4, 64, 8, 8, 40, 4, 4],
    "single": [32],
}


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _release(req, order, idx, check):
    """Release every bucket the way ``order`` names; ``check`` runs
    after each single ``pready`` with the set released so far."""
    if order == "pready_range":
        req.pready_range(0, len(idx) - 1)
    elif order == "pready_list":
        req.pready_list(reversed(idx))
    elif order == "threads":
        import threading

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            lanes = [threading.Thread(target=req.pready_list,
                                      args=(idx[k::4],)) for k in range(4)]
            for t in lanes:
                t.start()
            for t in lanes:
                t.join(60)
            assert not any(t.is_alive() for t in lanes)
        finally:
            sys.setswitchinterval(old)
    else:
        seq = {"last_to_first": idx[::-1], "first_to_last": idx,
               "shuffled": list(np.random.RandomState(5).permutation(idx))
               }[order]
        released = set()
        for p in seq:
            req.pready(p)
            released.add(int(p))
            check(released)


@pytest.mark.parametrize("order", ["last_to_first", "first_to_last",
                                   "shuffled", "pready_range",
                                   "pready_list", "threads"])
@pytest.mark.parametrize("bar", [1, 128, 1 << 30])
@pytest.mark.parametrize("sizes", list(_BUCKET_ELEMS))
def test_pallreduce_groups_launch_as_planned(world, pgroup_bar, sizes,
                                             bar, order):
    """One launch a planned group, whatever the release order: every
    result equals the per-bucket program's bit for bit, a group launches
    with its last member (a bucket at or over the bar at its own
    pready), SPC ``device_collectives`` counts buckets and not launches,
    and nothing is built after ``pallreduce_init``."""
    from ompi_tpu.mca.coll.xla import plan_groups
    from ompi_tpu.runtime import spc

    elems = _BUCKET_ELEMS[sizes]
    n, B = world.size, len(elems)
    idx = list(range(B))
    m = _xla(world)
    rng = np.random.RandomState(11)
    epochs = [[m.make_world_array(rng.normal(size=(n, e))
                                  .astype(np.float32)) for e in elems]
              for _ in range(2)]
    want = [[_bits(world.allreduce_array(b)) for b in bs] for bs in epochs]
    plan = plan_groups([4 * e for e in elems], bar)
    assert sorted(i for g in plan for i in g) == idx
    if bar == 1:
        assert plan == [(i,) for i in idx]
    if bar == 1 << 30:
        assert plan == [tuple(idx)]

    pgroup_bar(bar)
    c0 = spc.read("device_collectives")
    req = world.pallreduce_init(epochs[0])
    assert spc.read("device_collectives") - c0 == B
    assert [members for members, _ in req._plan] == plan
    builds = spc.read("device_program_builds")

    def check(released):
        for members in plan:
            launched = [req.result[i] is not None for i in members]
            assert all(launched) == set(members).issubset(released)
            assert any(launched) == all(launched)

    launches = []
    for buckets, bits in zip(epochs, want):
        c0 = spc.read("device_collectives")
        l0 = spc.read("part_group_launches")
        req.start(buckets)
        _release(req, order, idx, check)
        req.wait()
        for got, ref in zip(req.result, bits):
            np.testing.assert_array_equal(_bits(got), ref)
        assert spc.read("device_collectives") - c0 == B
        launches.append(spc.read("part_group_launches") - l0)
    assert launches == [len(plan)] * 2
    assert spc.read("device_program_builds") == builds


def _arrives(req, p, seconds: float = 60.0) -> bool:
    """Poll ``parrived(p)`` until it is true: the dispatch is async, and
    a count of polls instead of a deadline fails on a loaded host."""
    import time

    deadline = time.monotonic() + seconds
    while not req.parrived(p) and time.monotonic() < deadline:
        time.sleep(0)
    return req.parrived(p)


@pytest.mark.parametrize("poll", ["parrived", "test"])
def test_pallreduce_poll_progresses_a_waiting_bucket(world, pgroup_bar,
                                                     poll):
    """A caller that polls one bucket before it produces the next still
    gets its result: ``parrived`` (and ``test``) dispatch a released
    bucket whose group waits for members through its own program, and
    the group's later members still arrive."""
    from ompi_tpu.runtime import spc

    n = world.size
    buckets = [np.full((n, 4), float(i + 1), np.float32)
               for i in range(4)]
    pgroup_bar(1 << 30)                     # one group of four
    req = world.pallreduce_init(buckets)
    builds = spc.read("device_program_builds")
    l0 = spc.read("part_group_launches")
    req.start()
    req.pready(3)
    assert req.result[3] is None            # waits for its group
    assert not req.parrived(2)              # not released: no dispatch
    assert req.result[2] is None
    if poll == "test":
        assert req.test() == (False, None)
    assert _arrives(req, 3)
    assert spc.read("part_group_launches") - l0 == 1
    for i in (1, 0, 2):
        req.pready(i)
    req.wait()
    for i in range(4):
        assert _arrives(req, i)
        np.testing.assert_allclose(np.asarray(req.result[i]), (i + 1) * n)
    assert spc.read("part_group_launches") - l0 == 4
    assert spc.read("device_program_builds") == builds
    # the next epoch, not polled, is one launch again
    req.start()
    req.pready_list(range(4))
    req.wait()
    assert spc.read("part_group_launches") - l0 == 5


@pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0)])
def test_pallreduce_failed_group_dispatch_does_not_wedge(world, pgroup_bar,
                                                         order):
    """A group dispatch that raises (a rebind with one member
    mismatching the bound template) un-marks the bucket whose pready
    triggered it: the same error on retry, never 'already released',
    and the request stays restartable."""
    n = world.size
    good = [np.full((n, 4), float(i + 1), np.float32) for i in range(3)]
    pgroup_bar(1 << 30)
    req = world.pallreduce_init(good)
    bad = list(good)
    bad[1] = np.ones((n + 1, 4), np.float32)   # not divisible by the mesh
    req.start(bad)
    req.pready(order[0])
    req.pready(order[1])                       # joins its group: no launch
    with pytest.raises(Exception) as first:
        req.pready(order[2])
    assert "already released" not in str(first.value)
    with pytest.raises(Exception) as again:    # rollback: same error
        req.pready(order[2])
    assert type(again.value) is type(first.value)
    assert "already released" not in str(again.value)
    with pytest.raises(MpiError, match="already released"):
        req.pready(order[0])                   # the others stay released
    assert req.result == [None] * 3
    req.free()
    req.start(good)
    req.pready_list(order)
    req.wait()
    for i in range(3):
        np.testing.assert_allclose(np.asarray(req.result[i]), (i + 1) * n)


def test_pallreduce_failed_poll_after_last_pready_ends_the_wait(
        world, pgroup_bar):
    """A poll's dispatch that fails after the group's last member was
    released (by another thread, here from inside the failing dispatch)
    has no ``pready`` left to take its buckets up again: the request
    completes in error, ``wait()`` raises it and does not spin, and the
    request restarts."""
    n = world.size
    good = [np.full((n, 4), float(i + 1), np.float32) for i in range(3)]
    pgroup_bar(1 << 30)
    req = world.pallreduce_init(good)
    handle = req._handles[1]

    def racing(x):
        req.pready(2)                # the last member arrives meanwhile
        raise ValueError("bucket 1 cannot be dispatched")

    req._handles[1] = racing
    req.start()
    req.pready(0)
    req.pready(1)
    with pytest.raises(ValueError, match="bucket 1"):
        req.parrived(0)              # claims 0 and 1; 1 fails
    assert req.result[2] is not None     # launched alone, as a poll left it
    assert req.result[0] is None and req.result[1] is None
    with pytest.raises(MpiError, match="bucket 1 cannot be dispatched"):
        req.wait(timeout=30)
    done, status = req.get_status()
    assert done and status.error == ErrorClass.ERR_OTHER
    req._handles[1] = handle
    req.start()
    req.pready_list(range(3))
    req.wait()
    for i in range(3):
        np.testing.assert_allclose(np.asarray(req.result[i]), (i + 1) * n)


def test_partitioned_coll_is_the_allreduce_only(world):
    with pytest.raises(MpiError, match="no partitioned device collective"):
        _xla(world).partitioned_coll(world, "allgather", [np.ones(
            (world.size, 4), np.float32)])


def test_part_framework_discovered_by_otpu_info():
    """Satellite: the part framework (single default component) must be
    auto-discovered and its cvars visible under --all/--parsable."""
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.otpu_info", "--all",
         "--parsable"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    assert "mca part:persist (priority 20)" in r.stdout
    assert "mca var otpu_part_persist_min_partitions:1" in r.stdout


def test_part_spans_and_counters(world, min_partitions):
    """Observability satellite: pready spans + part_* SPC counters."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.runtime import spc, trace

    registry.set("otpu_trace_enable", True)
    trace.reset_for_testing()
    try:
        a, b = world.as_rank(0), world.as_rank(3)
        x = np.arange(16.0)
        y = np.zeros(16)
        c0 = spc.read("part_pready")
        s = a.psend_init(x, 4, dest=3, tag=19)
        r = b.precv_init(y, 4, source=0, tag=19)
        start_all([s, r])
        s.pready_range(0, 3)
        s.wait()
        r.wait()
        assert spc.read("part_pready") - c0 == 4
        assert spc.read("part_bytes") > 0
        names = {e[1] for e in trace._ring if e is not None}
        assert "pready" in names, names
        assert "part_arrive" in names, names
        assert any(k[0] == "pready" for k in trace.histograms()), \
            trace.histograms().keys()
    finally:
        registry.set("otpu_trace_enable", False)
        trace.reset_for_testing()
