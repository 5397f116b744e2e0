"""The multi-process device world (VERDICT round-5 item 1): tpurun
``--device-world`` boots ``jax.distributed`` in every rank through the
instance layer — coordinator address from the coord service, process_id
from the rank map, gloo CPU collectives — so one compiled XLA program
spans processes.  The acceptance shape: a ``coll/xla`` allreduce AND one
flagship ``train_step`` execute across a REAL process boundary
(2 processes × 4 virtual CPU devices), with the communicator built via
``Group_from_session_pset`` + ``Comm_create_from_group`` and NO
MPI_Init anywhere in the rank program.
"""
import os
import subprocess
import sys
import textwrap

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_world_job(script, n=2, local=4, timeout=540):
    return tpurun(n, script, timeout=timeout,
                  extra=("--device-world", "--local-devices", str(local)),
                  env={"XLA_FLAGS": None})


def test_session_device_allreduce_and_train_step_cross_process(tmp_path):
    """The done-criterion test: sessions-model construction end to end,
    device collective + train step crossing the process boundary."""
    script = tmp_path / "dw.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu
        from ompi_tpu.api.errhandler import ERRORS_RETURN

        # sessions model only — MPI_Init must never run in this program
        s = ompi_tpu.Session.init(errhandler=ERRORS_RETURN)
        assert not ompi_tpu.initialized()

        import jax
        assert jax.process_count() == 2, jax.process_count()
        assert len(jax.devices()) == 8, len(jax.devices())
        assert len(jax.local_devices()) == 4

        g = ompi_tpu.Group.from_session_pset(s, "mpi://WORLD")
        comm = ompi_tpu.Comm.create_from_group(g, "ci-device-world")
        assert comm.size == 2

        # the comm's device slots must be the coll/xla cross-process
        # module, not a host fallback
        from ompi_tpu.mca.coll.xla import XlaMpCollModule
        slot = comm.c_coll["allreduce_array"]
        while hasattr(slot, "__wrapped__"):
            slot = slot.__wrapped__
        assert isinstance(slot.__self__, XlaMpCollModule), slot

        # allreduce across the process boundary: each process
        # contributes rank+1, the sum needs BOTH processes' rows
        x = np.full((3,), float(comm.rank + 1), np.float32)
        y = comm.allreduce_array(x)
        got = np.asarray(y).ravel()
        assert got.tolist() == [3.0] * 3, got
        # bcast from the OTHER process + allgather of both rows
        b = comm.bcast_array(
            np.array([41.0 + comm.rank], np.float32), root=1)
        assert float(np.asarray(b)[0]) == 42.0
        ag = comm.allgather_array(np.array([comm.rank], np.int32))
        assert np.asarray(ag).ravel().tolist() == [0, 1]
        print(f"DWCOLL OK {comm.rank}", flush=True)

        # one flagship train step over the GLOBAL mesh: dp/sp/tp psums
        # ride gloo across the boundary inside one jitted program
        from ompi_tpu.parallel.dryrun import make_step_and_args
        step, (params, xd), mspec = make_step_and_args(jax.devices())
        new_params, loss = step(params, xd)
        jax.block_until_ready(new_params)
        loss = float(loss)
        _, loss2 = step(new_params, xd)
        assert float(loss2) < loss, (loss, float(loss2))
        print(f"DWTRAIN OK {comm.rank} mesh {mspec.sizes()} "
              f"loss {loss:.4f}->{float(loss2):.4f}", flush=True)
        comm.free()
        s.finalize()
    """))
    r = _device_world_job(script)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("DWCOLL OK") == 2, r.stdout + r.stderr
    assert r.stdout.count("DWTRAIN OK") == 2, r.stdout + r.stderr


def test_dryrun_multichip_two_process_mode():
    """``dryrun_multichip(8, nprocs=2)``: the driver's dry run in its
    multi-process shape — 2 ranks × 4 virtual devices, full descending
    train step over the global mesh."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("OTPU_RANK", "OTPU_NPROCS", "OTPU_COORD", "XLA_FLAGS"):
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable, "-c",
         f"import sys; sys.path.insert(0, {REPO!r}); "
         "import __graft_entry__ as g; g.dryrun_multichip(8, nprocs=2)"],
        capture_output=True, text=True, timeout=540, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("dryrun ok") == 2, r.stdout + r.stderr


def test_device_world_reinit_same_process(tmp_path):
    """World-model re-init must survive an already-initialized
    jax.distributed client: init → finalize → init in a device-world
    rank reuses the live distributed runtime instead of re-dialing."""
    script = tmp_path / "dwreinit.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu

        w = ompi_tpu.init()
        assert getattr(w.rte, "device_world_booted", False)
        y = w.allreduce_array(np.ones(1, np.float32))
        assert float(np.asarray(y)[0]) == 2.0
        ompi_tpu.finalize()
        w = ompi_tpu.init()          # second boot, same jax client
        assert getattr(w.rte, "device_world_booted", False)
        y = w.allreduce_array(np.full(1, 2.0, np.float32))
        assert float(np.asarray(y)[0]) == 4.0
        print(f"DWREINIT OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _device_world_job(script)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("DWREINIT OK") == 2, r.stdout + r.stderr
