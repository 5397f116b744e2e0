"""MPI-4 Sessions (``ompi_tpu/instance`` + ``api/session.py``): boot
without MPI_Init, pset enumeration, sessions-model communicator
construction, instance refcount interleavings with the world model, and
the error paths.

Single-process tests run against the conductor device world (conftest's
8 virtual devices); the multiprocess cases launch real tpurun jobs where
psets come from the coord service.
"""
import functools
import os
import random
import textwrap

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api.errhandler import ERRORS_RETURN
from ompi_tpu.api.errors import ErrorClass, MpiError

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(tpurun, timeout=300)


@pytest.fixture(autouse=True)
def _fresh_runtime():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    yield
    rt.reset_for_testing()


# -- sessions without MPI_Init ------------------------------------------

def test_session_boots_without_world_init():
    from ompi_tpu import instance as inst_mod

    s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    assert not ompi_tpu.initialized()          # no MPI_Init happened
    assert inst_mod.refcount() == 1
    names = s.psets()
    assert "mpi://WORLD" in names and "mpi://SELF" in names
    assert s.get_num_psets() == len(names)
    assert s.get_nth_pset(0) == names[0]
    info = s.get_pset_info("mpi://WORLD")
    assert int(info.get("mpi_size")) == len(
        s.group_from_pset("mpi://WORLD"))
    g = ompi_tpu.Group.from_session_pset(s, "mpi://SELF")
    assert g.size == 1
    s.finalize()
    assert inst_mod.refcount() == 0


def test_session_comm_from_pset_collectives():
    s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    g = s.group_from_pset("mpi://WORLD")
    comm = ompi_tpu.Comm.create_from_group(g, "t0")
    assert comm is not None and comm.size == g.size
    assert comm.cid >= 2          # 0/1 stay reserved for WORLD/SELF
    y = comm.allreduce_array(np.ones((comm.size, 2), np.float32))
    assert float(np.asarray(y).ravel()[0]) == comm.size
    comm.free()
    s.finalize()


def test_two_concurrent_sessions_disjoint_comms():
    s1 = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    s2 = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    world = s1.group_from_pset("mpi://WORLD")
    n = world.size
    g1 = world.incl(range(n // 2))
    g2 = world.incl(range(n // 2, n))
    c1 = ompi_tpu.Comm.create_from_group(g1, "lo")
    c2 = ompi_tpu.Comm.create_from_group(g2, "hi")
    assert c1.cid != c2.cid
    assert set(c1.group.world_ranks).isdisjoint(c2.group.world_ranks)
    y1 = c1.allreduce_array(np.ones((c1.size, 1), np.float32))
    y2 = c2.allreduce_array(np.full((c2.size, 1), 2.0, np.float32))
    assert float(np.asarray(y1).ravel()[0]) == c1.size
    assert float(np.asarray(y2).ravel()[0]) == 2.0 * c2.size
    # finalizing the session that built c1 must not kill the runtime
    # (s2 still holds a reference) nor c1 itself (comms are independent
    # objects per MPI-4)
    s1.finalize()
    y1b = c1.allreduce_array(np.ones((c1.size, 1), np.float32))
    assert float(np.asarray(y1b).ravel()[0]) == c1.size
    c1.free()
    c2.free()
    s2.finalize()


def test_intercomm_create_from_groups_single_process():
    s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    world = s.group_from_pset("mpi://WORLD")
    n = world.size
    lo = world.incl(range(n // 2))
    hi = world.incl(range(n // 2, n))
    # the conductor hosts rank 0, so the lo side is "my" side
    inter = ompi_tpu.Comm.create_intercomm_from_groups(
        lo, 0, hi, 0, "bridge")
    assert inter.is_inter
    assert inter.size == lo.size and inter.remote_size == hi.size
    assert inter.local_comm.size == lo.size
    with pytest.raises(MpiError):
        ompi_tpu.Comm.create_intercomm_from_groups(
            lo, 0, world, 0, "overlap")     # groups overlap
    inter.free()
    s.finalize()


# -- world init + session refcount interleavings ------------------------

def test_world_and_session_share_one_boot():
    from ompi_tpu import instance as inst_mod

    s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    inst_before = inst_mod.current()
    w = ompi_tpu.init()
    # world init joined the session's boot instead of re-booting
    assert inst_mod.current() is inst_before
    assert inst_mod.refcount() == 2
    assert w.rte is inst_before.rte
    ompi_tpu.finalize()
    # the session keeps the runtime alive past world finalize
    assert ompi_tpu.finalized()
    assert inst_mod.refcount() == 1
    g = s.group_from_pset("mpi://WORLD")
    c = ompi_tpu.Comm.create_from_group(g, "post-finalize")
    y = c.allreduce_array(np.ones((c.size, 1), np.float32))
    assert float(np.asarray(y).ravel()[0]) == c.size
    c.free()
    s.finalize()
    assert inst_mod.refcount() == 0


def test_init_finalize_init_under_refcounting():
    """The MPI-4 relaxation: MPI_Init after MPI_Finalize works (each
    init/finalize pair is one acquire/release of the instance)."""
    w1 = ompi_tpu.init()
    size1 = w1.size
    assert np.asarray(w1.allreduce(np.ones((size1, 1))))[0] == size1
    ompi_tpu.finalize()
    assert ompi_tpu.finalized()
    w2 = ompi_tpu.init()
    assert not ompi_tpu.finalized() and ompi_tpu.initialized()
    assert w2.size == size1
    assert np.asarray(w2.allreduce(np.ones((size1, 1))))[0] == size1
    ompi_tpu.finalize()


def test_finalize_order_fuzz():
    """Random interleavings of session opens/finalizes and world
    init/finalize: every order must keep the refcount consistent, end
    fully torn down, and allow the next round to boot."""
    from ompi_tpu import instance as inst_mod

    rng = random.Random(7)
    for round_no in range(4):
        owners = []      # closers, in open order
        n_open = rng.randint(1, 4)
        world_open = False
        for _ in range(n_open):
            if not world_open and rng.random() < 0.4:
                ompi_tpu.init()
                owners.append(ompi_tpu.finalize)
                world_open = True
            else:
                s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
                owners.append(s.finalize)
        assert inst_mod.refcount() == len(owners)
        rng.shuffle(owners)
        for i, close in enumerate(owners):
            close()
            assert inst_mod.refcount() == len(owners) - i - 1
        assert inst_mod.current() is None, f"round {round_no}"


# -- error paths --------------------------------------------------------

def test_session_error_paths():
    s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
    with pytest.raises(MpiError) as exc:
        s.get_pset_info("mpi://no-such-set")
    assert exc.value.error_class == ErrorClass.ERR_ARG
    with pytest.raises(MpiError):
        s.group_from_pset("mpi://no-such-set")
    with pytest.raises(MpiError):
        s.get_nth_pset(10**6)
    s.finalize()
    # every post-finalize use is ERR_SESSION
    for call in (s.finalize, s.get_num_psets, s.psets,
                 lambda: s.group_from_pset("mpi://WORLD"),
                 lambda: s.get_pset_info("mpi://WORLD"),
                 s.get_info):
        with pytest.raises(MpiError) as exc:
            call()
        assert exc.value.error_class == ErrorClass.ERR_SESSION


def test_create_from_group_needs_instance():
    with pytest.raises(MpiError) as exc:
        ompi_tpu.Comm.create_from_group(ompi_tpu.Group([0]), "orphan")
    assert exc.value.error_class == ErrorClass.ERR_SESSION


def test_session_info_and_errhandler():
    from ompi_tpu.api.info import Info

    info = Info({"app": "test"})
    s = ompi_tpu.Session.init(info=info, errhandler=ERRORS_RETURN)
    got = s.get_info()
    assert got.get("app") == "test"
    assert got.get("thread_level") == "MPI_THREAD_MULTIPLE"
    assert s.get_errhandler() is ERRORS_RETURN
    with pytest.raises(MpiError):
        s.call_errhandler(int(ErrorClass.ERR_OTHER))
    s.finalize()


# -- multiprocess: psets from the coord service -------------------------

def test_mp_sessions_psets_and_comms(tmp_path):
    """Sessions across real processes, NO MPI_Init anywhere: coord-
    served psets (builtin world, per-host, user --pset), the sessions-
    model construction chain, and an intercomm from bare groups."""
    script = tmp_path / "sess.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu
        from ompi_tpu.api.errhandler import ERRORS_RETURN

        s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
        assert not ompi_tpu.initialized()
        names = s.psets()
        assert "mpi://WORLD" in names and "evens" in names, names
        assert any(n.startswith("mpi://host/") for n in names), names
        g = ompi_tpu.Group.from_session_pset(s, "mpi://WORLD")
        comm = ompi_tpu.Comm.create_from_group(g, "app")
        out = comm.allreduce(np.array([float(comm.rank + 1)]))
        assert float(np.asarray(out)[0]) == 6.0, out   # 1+2+3
        ge = s.group_from_pset("evens")
        assert ge.world_ranks == (0, 2), ge
        info = s.get_pset_info("evens")
        assert info.get("mpi_size") == "2"
        assert info.get("otpu_source") == "user"
        ce = ompi_tpu.Comm.create_from_group(ge, "even-side")
        if comm.rank % 2 == 0:
            assert ce is not None and ce.size == 2
            out = ce.allreduce(np.array([1.0]))
            assert float(np.asarray(out)[0]) == 2.0
            ce.free()
        else:
            assert ce is None      # not a member
        # intercomm from bare groups: evens vs odds
        godd = g.difference(ge)
        mine, other = (ge, godd) if comm.rank % 2 == 0 else (godd, ge)
        inter = ompi_tpu.Comm.create_intercomm_from_groups(
            mine, 0, other, 0, "eo")
        assert inter.is_inter and inter.remote_size == other.size
        if comm.rank == 0:
            inter.send(np.array([5.0]), dest=0, tag=2)
        elif comm.rank == 1:
            buf = np.zeros(1)
            inter.recv(buf, source=0, tag=2)
            assert buf[0] == 5.0
        print(f"MPSESS OK {comm.rank}", flush=True)
        inter.free(); comm.free()
        s.finalize()
    """))
    r = _tpurun(3, script, extra=("--pset", "evens:0,2"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("MPSESS OK") == 3, r.stdout + r.stderr


def test_mp_world_init_after_finalize(tmp_path):
    """Init → finalize → init across real processes: the second world
    boots a fresh RTE boot-to-boot (new fences, new modex) and its
    collectives still work."""
    script = tmp_path / "reinit.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu

        w = ompi_tpu.init()
        assert float(np.asarray(w.allreduce(np.ones(1)))[0]) == w.size
        ompi_tpu.finalize()
        w = ompi_tpu.init()
        assert float(np.asarray(w.allreduce(np.ones(1)))[0]) == w.size
        print(f"REINIT OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("REINIT OK") == 2, r.stdout + r.stderr


def test_mp_shrink_publishes_surviving_pset(tmp_path):
    """The ULFM recovery hook: after a rank dies, the coord service
    advertises ``mpi://surviving`` and shrink publishes the agreed
    survivor set as a dynamic pset a session can resolve by name."""
    script = tmp_path / "shrink_pset.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        import numpy as np
        import ompi_tpu

        w = ompi_tpu.init()
        if w.rank == 1:
            os._exit(17)          # die without finalize
        deadline = time.time() + 30
        from ompi_tpu.ft import state as ft_state
        while not ft_state.is_failed(1):
            time.sleep(0.1)
            assert time.time() < deadline, "failure never detected"
        sub = w.shrink()
        assert 1 not in sub.group.world_ranks
        s = ompi_tpu.Session.init()
        names = s.psets()
        assert "mpi://surviving" in names, names
        surv = s.group_from_pset("mpi://surviving")
        assert 1 not in surv.world_ranks and w.rank in surv.world_ranks
        shrunk = [n for n in names if n.startswith("mpi://shrunk/")]
        assert shrunk, names
        g2 = s.group_from_pset(shrunk[0])
        assert tuple(g2.world_ranks) == tuple(sub.group.world_ranks)
        print(f"SHRINKPSET OK {w.rank}", flush=True)
        s.finalize()
        ompi_tpu.finalize()
    """))
    r = _tpurun(3, script, extra=("--enable-recovery",))
    assert r.stdout.count("SHRINKPSET OK") == 2, r.stdout + r.stderr
