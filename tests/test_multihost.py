"""Multi-host wire-up: external coord service + per-"host" rank launch.

The reference's multi-node story (SURVEY §3.4): a launcher starts daemons
per host, procs PMIx_Init back to them.  Our equivalent: any external
launcher (slurm/k8s) exports ``OTPU_COORD`` pointing at the coord service
and per-rank identity env — exactly what this test does by hand, WITHOUT
tpurun, across two emulated hosts (``OTPU_NODE_ID`` hostA/hostB).

Asserts the transport matrix is what a two-host job must produce: btl/sm
within a host, btl/tcp (the DCN path) across hosts — the hook/comm_method
dump decision, selected per-peer by bml/r2 from modexed node identity.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from ompi_tpu.rte.coord import CoordServer

_WORKER = textwrap.dedent("""
    import os
    import numpy as np
    import ompi_tpu

    w = ompi_tpu.init()
    rank, n = w.rank, w.size
    me_node = os.environ["OTPU_NODE_ID"]

    # transport matrix: same-node neighbour via sm, cross-node via tcp
    pml = w.pml
    inner = getattr(pml, "_inner", pml)       # unwrap monitoring/vprotocol
    while hasattr(inner, "_inner"):
        inner = inner._inner
    bml = inner.bml
    same = rank ^ 1            # ranks 0,1 on hostA; 2,3 on hostB
    cross = (rank + 2) % n
    ep_same = bml.endpoint(same)
    ep_cross = bml.endpoint(cross)
    assert ep_same.btl.name == "sm", f"want sm intra-node, got {ep_same.btl.name}"
    assert ep_cross.btl.name == "tcp", f"want tcp inter-node, got {ep_cross.btl.name}"

    # cross-host p2p over tcp
    if rank == 0:
        w.send(np.arange(5.0), dest=2, tag=3)
    elif rank == 2:
        buf = np.zeros(5)
        st = w.recv(buf, source=0, tag=3)
        assert buf.tolist() == [0, 1, 2, 3, 4]

    # world collective spanning both hosts
    out = w.allreduce(np.array([rank + 1.0]))
    assert out[0] == n * (n + 1) / 2, out

    # han two-level composition must see 2 nodes x 2 ranks
    color = w.split_type("shared").size
    assert color == 2, f"intra-node comm size {color}"
    print(f"MULTIHOST_OK rank={rank} node={me_node}")
    ompi_tpu.finalize()
""")


def test_two_emulated_hosts_external_launcher(tmp_path):
    n = 4
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    server = CoordServer(nprocs=n)
    host, port = server.addr
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    try:
        for rank in range(n):
            env = dict(os.environ)
            env.update({
                "OTPU_COORD": f"{host}:{port}",
                "OTPU_RANK": str(rank),
                "OTPU_NPROCS": str(n),
                "OTPU_NODE_ID": "hostA" if rank < 2 else "hostB",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": pkg_root + os.pathsep
                + env.get("PYTHONPATH", ""),
            })
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=100)
            outs.append(out)
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {rank} failed:\n{out}"
            assert "MULTIHOST_OK" in out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.close()


_FT_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    import ompi_tpu
    from ompi_tpu.ft import state as ft_state

    w = ompi_tpu.init()
    rank, n = w.rank, w.size
    w.barrier()              # transports up, endpoints warmed, hb flowing
    print(f"READY {rank}", flush=True)
    if rank == 2:
        sys.stdin.readline()   # parent signals AFTER killing the coord
        os._exit(1)            # die abruptly with the coord already gone
    deadline = time.monotonic() + 40
    while time.monotonic() < deadline:
        if ft_state.is_failed(2):
            print(f"DETECTED {rank}", flush=True)
            os._exit(0)      # coord is dead: no clean finalize possible
        time.sleep(0.2)
    print(f"TIMEOUT {rank}", flush=True)
    os._exit(3)
""")


def test_detector_survives_coord_death(tmp_path):
    """VERDICT weak #4: the failure detector must not ride the coord
    SPOF.  Wire up 3 ranks, KILL the coordination service, then kill a
    rank — survivors must still detect it via p2p btl heartbeats
    (``comm_ft_detector.c``'s active-message carrier + the propagator's
    p2p flood)."""
    import threading
    import time

    n = 3
    script = tmp_path / "ft_worker.py"
    script.write_text(_FT_WORKER)
    server = CoordServer(nprocs=n)
    host, port = server.addr
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    outs = {i: "" for i in range(n)}
    ready = {i: threading.Event() for i in range(n)}

    def pump(i, p):
        for line in p.stdout:
            outs[i] += line
            if "READY" in line:
                ready[i].set()

    pumps = []
    try:
        for rank in range(n):
            env = dict(os.environ)
            env.update({
                "OTPU_COORD": f"{host}:{port}",
                "OTPU_RANK": str(rank),
                "OTPU_NPROCS": str(n),
                "JAX_PLATFORMS": "cpu",
                "OTPU_MCA_ft_detector": "1",
                "OTPU_MCA_ft_detector_period": "0.3",
                "OTPU_MCA_ft_detector_timeout": "2.0",
                "OTPU_MCA_ft_detector_startup_grace": "2.0",
                "PYTHONPATH": pkg_root + os.pathsep
                + env.get("PYTHONPATH", ""),
            })
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        pumps = [threading.Thread(target=pump, args=(i, p), daemon=True)
                 for i, p in enumerate(procs)]
        for t in pumps:
            t.start()
        for i in range(n):
            assert ready[i].wait(90), (i, outs)
        server.close()            # <-- the SPOF dies here, BEFORE the kill
        time.sleep(0.5)
        procs[2].stdin.write("die\n")
        procs[2].stdin.close()
        rcs = {}
        for i, p in enumerate(procs):
            rcs[i] = p.wait(timeout=60)
        for t in pumps:
            t.join(5)
        assert rcs[2] == 1                      # the killed rank
        for i in (0, 1):
            assert "DETECTED" in outs[i], (i, outs[i], rcs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        try:
            server.close()
        except Exception:
            pass
