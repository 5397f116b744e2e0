"""ULFM fault tolerance: failure state, revoke/shrink/agree, detector,
recovery-mode launcher (SURVEY.md §3.5/§5.3)."""
import textwrap
from pathlib import Path

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.ft import state as ft_state
from ompi_tpu.runtime import init as rt

from launch import tpurun

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def world():
    from ompi_tpu.api.errhandler import ERRORS_RETURN

    rt.reset_for_testing()
    w = ompi_tpu.init()
    w.set_errhandler(ERRORS_RETURN)  # ULFM apps opt out of abort-on-error
    yield w
    rt.reset_for_testing()


class TestFailureState:
    def test_mark_and_listeners(self):
        ft_state.reset_for_testing()
        seen = []
        ft_state.on_failure(seen.append)
        ft_state.mark_failed(3)
        ft_state.mark_failed(3)  # dedup
        assert ft_state.is_failed(3)
        assert ft_state.failed_ranks() == frozenset({3})
        assert seen == [3]
        ft_state.reset_for_testing()

    def test_revoked_cids_epoch_scoped(self):
        ft_state.reset_for_testing()
        ft_state.mark_revoked(5, epoch=0)
        assert ft_state.is_comm_revoked(5, 0)
        assert not ft_state.is_comm_revoked(5, 1)  # reused CID, new epoch
        ft_state.reset_for_testing()


class TestDeviceWorldFt:
    def test_send_to_failed_rank_raises(self, world):
        from ompi_tpu.api.errors import ProcFailedError

        if world.size < 2:
            pytest.skip("needs >= 2 ranks in device world")
        ft_state.mark_failed(world.world_rank(1))
        with pytest.raises(ProcFailedError):
            world.as_rank(0).send(np.zeros(1), dest=1)
        assert world.get_failed().size == 1

    def test_revoke_then_ops_raise(self, world):
        from ompi_tpu.api.errors import RevokedError

        dup = world.dup()
        dup.revoke()
        assert dup.is_revoked()
        # a facade of the same comm (another "rank") sees the revocation
        # through the global FT state even though the flag was set on dup
        other = dup.as_rank(min(1, world.size - 1))
        other.revoked = False
        with pytest.raises(RevokedError):
            other.barrier()

    def test_shrink_excludes_failed(self, world):
        if world.size < 2:
            pytest.skip("needs >= 2 ranks")
        dead = world.world_rank(world.size - 1)
        ft_state.mark_failed(dead)
        s = world.shrink()
        assert s.size == world.size - 1
        assert dead not in s.group.world_ranks
        assert s.epoch == world.epoch + 1
        # shrunken comm is fully operational (conductor model: leading axis
        # indexes ranks)
        out = s.allreduce(np.ones((s.size, 4)))
        assert out.tolist() == [float(s.size)] * 4

    def test_ack_failed(self, world):
        if world.size < 2:
            pytest.skip("needs >= 2 ranks")
        ft_state.mark_failed(world.world_rank(1))
        assert world.ack_failed() == 1


def _job(n, script, timeout=180, recovery=False, mca=()):
    extra = ["--enable-recovery"] if recovery else []
    for k, v in mca:
        extra += ["--mca", k, v]
    return tpurun(n, script, timeout=timeout, extra=extra)


class TestMultiprocessFt:
    def test_launcher_detects_death_survivors_shrink(self, tmp_path):
        script = tmp_path / "ft.py"
        script.write_text(textwrap.dedent("""
            import os, sys, time
            import numpy as np
            import ompi_tpu
            from ompi_tpu.ft import state as ft_state

            w = ompi_tpu.init()
            w.barrier()
            if w.rank == 1:
                os._exit(13)  # sudden death, no cleanup
            deadline = time.time() + 60
            while not ft_state.is_failed(1):
                if time.time() > deadline:
                    sys.exit("failure of rank 1 never detected")
                time.sleep(0.05)
            assert w.get_failed().size == 1
            s = w.shrink()
            assert s.size == 3, s.size
            assert s.epoch == 1
            out = s.allreduce(np.array([float(s.rank + 1)]))
            assert out[0] == 6.0, out
            if s.rank == 0:
                print("FT SHRINK OK")
            ompi_tpu.finalize()
        """))
        r = _job(4, script, recovery=True)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "FT SHRINK OK" in r.stdout

    def test_agree_with_failure_and_ack(self, tmp_path):
        script = tmp_path / "agree.py"
        script.write_text(textwrap.dedent("""
            import os, sys, time
            import ompi_tpu
            from ompi_tpu.api.errors import ProcFailedError
            from ompi_tpu.api.errhandler import ERRORS_RETURN
            from ompi_tpu.ft import state as ft_state

            w = ompi_tpu.init()
            w.set_errhandler(ERRORS_RETURN)
            # first: agreement with everyone alive ANDs the flags
            got = w.agree(0b1110 if w.rank else 0b0111)
            assert got == 0b0110, got
            w.barrier()
            if w.rank == 2:
                os._exit(7)
            deadline = time.time() + 60
            while not ft_state.is_failed(2):
                if time.time() > deadline:
                    sys.exit("no detection")
                time.sleep(0.05)
            # unacknowledged failure -> uniform ProcFailedError, flag agreed
            try:
                w.agree(0b11)
                sys.exit("expected ProcFailedError")
            except ProcFailedError as e:
                assert e.flag == 0b11, e.flag
            w.ack_failed()
            assert w.agree(0b11) == 0b11
            if w.rank == 0:
                print("FT AGREE OK")
            ompi_tpu.finalize()
        """))
        r = _job(3, script, recovery=True)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "FT AGREE OK" in r.stdout

    def test_revoke_propagates_between_processes(self, tmp_path):
        script = tmp_path / "revoke.py"
        script.write_text(textwrap.dedent("""
            import sys, time
            import ompi_tpu
            from ompi_tpu.api.errors import RevokedError
            from ompi_tpu.api.errhandler import ERRORS_RETURN

            w = ompi_tpu.init()
            w.set_errhandler(ERRORS_RETURN)
            d = w.dup()
            if w.rank == 0:
                d.revoke()
            deadline = time.time() + 60
            while not d.is_revoked():
                if time.time() > deadline:
                    sys.exit("revocation never arrived")
                time.sleep(0.05)
            try:
                d.barrier()
                sys.exit("expected RevokedError")
            except RevokedError:
                pass
            w.barrier()  # parent comm unaffected
            if w.rank == 0:
                print("FT REVOKE OK")
            ompi_tpu.finalize()
        """))
        r = _job(3, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "FT REVOKE OK" in r.stdout

    def test_heartbeat_detector_finds_silent_peer(self, tmp_path):
        script = tmp_path / "hb.py"
        script.write_text(textwrap.dedent("""
            import sys, time
            import ompi_tpu
            from ompi_tpu.ft import state as ft_state
            from ompi_tpu.ft import propagator

            w = ompi_tpu.init()
            w.barrier()
            if w.rank == 1:
                # simulate a hang: the process stays alive (so the launcher
                # sees nothing) but its heartbeats stop.  Halt the emitter
                # thread WITHOUT the clean-finalize tombstone that stop()
                # would write -- a hang leaves no tombstone.
                propagator._detector._stop.set()
                # stay silent past detector_timeout (1.5s) + detection slack
                time.sleep(4)
                sys.exit(0)
            deadline = time.time() + 60
            while not ft_state.is_failed(1):
                if time.time() > deadline:
                    sys.exit("heartbeat detector never fired")
                time.sleep(0.05)
            if w.rank == 0:
                print("FT DETECTOR OK")
            ompi_tpu.finalize()
        """))
        r = _job(3, script, recovery=True, timeout=120,
                    mca=[("ft_detector", "true"),
                         ("ft_detector_period", "0.2"),
                         ("ft_detector_timeout", "1.5")])
        assert "FT DETECTOR OK" in r.stdout, r.stdout + r.stderr
        assert r.returncode == 0, r.stdout + r.stderr


class TestCoordFreeAgreement:
    def test_agree_survives_root_death_with_coord_gagged(self, tmp_path):
        """ERA p2p agreement: the tree ROOT (rank 0) dies mid-agreement
        while every survivor's coordination-service KV ops are gagged —
        decisions must ride only the p2p carrier (takeover root gathers
        pledge replies, decides, broadcasts).  The coord stays restricted
        to wire-up, matching ``coll_ftagree_earlyreturning.c``'s
        no-central-arbiter property."""
        script = tmp_path / "rootdeath.py"
        script.write_text(textwrap.dedent("""
            import os, sys, time
            import ompi_tpu
            from ompi_tpu.ft import state as ft_state

            w = ompi_tpu.init()
            w.barrier()
            if w.rank == 0:
                time.sleep(0.3)
                os._exit(11)   # the agreement tree's root dies
            # gag the shared coord client's KV surface: any decision-path
            # use of the coordination service now fails loudly
            client = w.rte.client
            def _gagged(*a, **k):
                raise AssertionError("agreement touched the coord service")
            client.get = _gagged
            client.put_new = _gagged
            client.delete = _gagged
            got = w.agree(0b1101 if w.rank == 1 else 0b0111)
            assert got == 0b0101, got
            # the agreed failed-set is uniform too: everyone saw rank 0
            deadline = time.time() + 60
            while not ft_state.is_failed(0):
                if time.time() > deadline:
                    sys.exit("root death never detected")
                time.sleep(0.05)
            w.ack_failed()
            got2 = w.agree(0b11)
            assert got2 == 0b11, got2
            print(f"ROOTDEATH OK {w.rank}", flush=True)
            ompi_tpu.finalize()
        """))
        r = _job(4, script, recovery=True, timeout=150,
                    mca=[("ft_detector", "true"),
                         ("ft_detector_period", "0.2"),
                         ("ft_detector_timeout", "1.5"),
                         ("ft_detector_startup_grace", "2.0")])
        assert r.stdout.count("ROOTDEATH OK") == 3, r.stdout + r.stderr
        assert r.returncode == 0, r.stdout + r.stderr

    def test_revoke_floods_with_event_bus_down(self, tmp_path):
        """Revocation propagation must not depend on the coordination
        service's event bus: stop the event poller on every rank, revoke,
        and require the p2p flood (``comm_ft_revoke.c`` resilient
        broadcast analog) to deliver it."""
        script = tmp_path / "revflood.py"
        script.write_text(textwrap.dedent("""
            import sys, time
            import ompi_tpu
            from ompi_tpu.api.errors import RevokedError
            from ompi_tpu.api.errhandler import ERRORS_RETURN
            from ompi_tpu.ft import propagator
            from ompi_tpu.runtime.progress import progress

            w = ompi_tpu.init()
            w.set_errhandler(ERRORS_RETURN)
            d = w.dup()
            # kill the event-bus leg everywhere: only the p2p flood remains
            propagator._poller.stop()
            w.barrier()
            if w.rank == 0:
                d.revoke()
            deadline = time.time() + 60
            while not d.is_revoked():
                if time.time() > deadline:
                    sys.exit("revocation never arrived over p2p")
                progress()   # a rank blocked in MPI drives the engine;
                             # the CTL flood rides it
                time.sleep(0.002)
            try:
                d.barrier()
                sys.exit("expected RevokedError")
            except RevokedError:
                pass
            print(f"REVFLOOD OK {w.rank}", flush=True)
            ompi_tpu.finalize()
        """))
        r = _job(3, script)
        assert r.stdout.count("REVFLOOD OK") == 3, r.stdout + r.stderr
        assert r.returncode == 0, r.stdout + r.stderr


class TestMultiFailure:
    def test_detector_survives_double_failure(self, tmp_path):
        """TWO adjacent ranks die; the ring rotates past both and every
        survivor learns both failures (observer rotation,
        ``comm_ft_detector.c`` + the propagator flood)."""
        script = tmp_path / "double.py"
        script.write_text(textwrap.dedent("""
            import os, sys, time
            import ompi_tpu
            from ompi_tpu.ft import state as ft_state

            w = ompi_tpu.init()
            w.barrier()
            if w.rank in (1, 2):
                time.sleep(0.5)
                os._exit(1)          # both die abruptly, no tombstone
            deadline = time.time() + 60
            while not (ft_state.is_failed(1) and ft_state.is_failed(2)):
                if time.time() > deadline:
                    sys.exit("double failure never fully detected")
                time.sleep(0.05)
            print(f"DOUBLE OK {w.rank}", flush=True)
            ompi_tpu.finalize()
        """))
        r = _job(4, script, recovery=True, timeout=150,
                    mca=[("ft_detector", "true"),
                         ("ft_detector_period", "0.2"),
                         ("ft_detector_timeout", "1.5"),
                         ("ft_detector_startup_grace", "2.0")])
        assert r.stdout.count("DOUBLE OK") == 2, r.stdout + r.stderr


class TestAgreementAlgorithms:
    def test_alternate_algorithms_agree(self, tmp_path):
        """The non-default agreement algorithms ('tree' = p2p reduce with
        KV-anchored decision, 'kv' = coordinator-decides) stay correct."""
        script = tmp_path / "alg.py"
        script.write_text(textwrap.dedent("""
            import ompi_tpu

            w = ompi_tpu.init()
            got = w.agree(0b1011 if w.rank % 2 else 0b1110)
            assert got == 0b1010, bin(got)
            print(f"ALG OK {w.rank}", flush=True)
            ompi_tpu.finalize()
        """))
        for alg in ("tree", "kv"):
            r = _job(3, script,
                        mca=[("coll_ftagree_algorithm", alg)])
            assert r.stdout.count("ALG OK") == 3, (alg, r.stdout + r.stderr)
            assert r.returncode == 0, (alg, r.stdout + r.stderr)
