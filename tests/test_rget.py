"""ob1 RGET protocol + btl one-sided put/get.

The reference's large-message ladder has eager / RNDV / RGET / RPUT
(``ompi/mca/pml/ob1/pml_ob1_sendreq.h:375-401``) over the btl RMA triple
(``opal/mca/btl/btl.h:949,987``).  These tests drive the new RGET branch
end-to-end over both transports: true one-sided segment pull on btl/sm,
request/stream emulation on btl/tcp (forced via --fake-nodes), plus the
raw btl put/get surface.
"""
import functools
import os
import textwrap

import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(launch.tpurun, timeout=240)


_LARGE_MSG = """
import numpy as np, ompi_tpu
from ompi_tpu.runtime import spc

w = ompi_tpu.init()
n = (3 << 20) // 8                       # 3MB float64 > rget_limit (1m)
if w.rank == 0:
    x = np.arange(n, dtype=np.float64)
    w.send(x, dest=1, tag=3)
    assert spc.read("rget_msgs") >= 1, "sender never took the RGET branch"
    # derived (vector) datatype: pack_borrow cannot hand out a view, so
    # RGET exposes the PACKED temporary — the non-borrowed branch
    from ompi_tpu.datatype import core
    nblk = n // 4
    dt = core.vector(nblk, 2, 4, core.FLOAT64)   # 2-of-4 stride pattern
    y = np.arange(4 * nblk, dtype=np.float64)
    w.send((y, 1, dt), dest=1, tag=4)
    print("SENDER OK", flush=True)
else:
    r = np.empty(n, np.float64)
    w.recv(r, source=0, tag=3)
    assert r[0] == 0 and r[-1] == n - 1 and r[n // 2] == n // 2, r
    nblk = n // 4
    r2 = np.empty(2 * nblk, np.float64)
    w.recv(r2, source=0, tag=4)
    # packed stream = elements 0,1, 4,5, 8,9, ... of the source
    assert r2[0] == 0 and r2[1] == 1 and r2[2] == 4 and r2[3] == 5, r2[:4]
    assert r2[-1] == 4 * (nblk - 1) + 1, r2[-1]
    print("RECEIVER OK", flush=True)
ompi_tpu.finalize()
"""


def test_rget_large_message_sm(tmp_path):
    script = tmp_path / "rget_sm.py"
    script.write_text(textwrap.dedent(_LARGE_MSG))
    r = _tpurun(2, script)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SENDER OK" in r.stdout and "RECEIVER OK" in r.stdout


def test_rget_large_message_tcp_emulated(tmp_path):
    # two fake nodes: sm declines cross-node, tcp carries the message and
    # RGET runs in pull-emulation mode (opt-in since round 4: emulation
    # measured slower than the FRAG stream, so it is gated by default)
    script = tmp_path / "rget_tcp.py"
    script.write_text(textwrap.dedent(_LARGE_MSG))
    r = _tpurun(2, script, extra=("--fake-nodes", "2",
                                  "--mca", "pml_ob1_rget_emulate", "1"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SENDER OK" in r.stdout and "RECEIVER OK" in r.stdout


def test_rget_not_engaged_on_non_rdma_btl_by_default(tmp_path):
    """Like the reference (RGET requires btl_get), the pull emulation on
    non-rdma btls is opt-in: a large tcp message with default vars must
    ride the FRAG stream (measured faster there), not RGET."""
    script = tmp_path / "norget.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.runtime import spc

        w = ompi_tpu.init()
        n = (3 << 20) // 8
        if w.rank == 0:
            w.send(np.arange(n, dtype=np.float64), dest=1, tag=3)
            assert spc.read("rget_msgs") == 0, \\
                "RGET emulation engaged on a non-rdma btl by default"
            print("GATED OK", flush=True)
        else:
            r = np.empty(n, np.float64)
            w.recv(r, source=0, tag=3)
            assert r[-1] == n - 1
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script, extra=("--fake-nodes", "2"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "GATED OK" in r.stdout


def test_rget_disabled_falls_back_to_rndv(tmp_path):
    script = tmp_path / "rndv.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.runtime import spc

        w = ompi_tpu.init()
        n = (3 << 20) // 8
        if w.rank == 0:
            w.send(np.arange(n, dtype=np.float64), dest=1, tag=3)
            assert spc.read("rget_msgs") == 0, "RGET engaged while disabled"
            print("RNDV OK", flush=True)
        else:
            r = np.empty(n, np.float64)
            w.recv(r, source=0, tag=3)
            assert r[-1] == n - 1
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script, extra=("--mca", "pml_ob1_rget_limit", "0"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RNDV OK" in r.stdout


def test_btl_sm_put_get_surface(tmp_path):
    """Raw btl RMA triple: prepare_src / get / put between two ranks."""
    script = tmp_path / "rma.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.mca.bml import resolve_bml
        from ompi_tpu.runtime import init as rt

        w = ompi_tpu.init()
        bml = resolve_bml(rt.get_world_if_initialized().pml)
        peer = 1 - w.rank
        ep = bml.endpoint(peer)
        assert ep.btl.name == "sm" and ep.btl.rdma
        src = np.arange(1024, dtype=np.uint8)
        key = ep.btl.prepare_src(ep, src)
        # exchange keys over p2p, then pull the peer's region
        import pickle
        kb = np.frombuffer(pickle.dumps(key), np.uint8)
        w.send(np.array([kb.size], np.int64), dest=peer, tag=8)
        w.send(kb, dest=peer, tag=9)
        ln = np.empty(1, np.int64)
        w.recv(ln, source=peer, tag=8)
        kbuf = np.empty(int(ln[0]), np.uint8)
        w.recv(kbuf, source=peer, tag=9)
        peer_key = pickle.loads(kbuf.tobytes())
        dst = np.zeros(1024, np.uint8)
        ep.btl.get(ep, dst, peer_key)
        assert np.array_equal(dst, src), "one-sided get corrupted data"
        # put: overwrite the peer's exposed region, then verify via get
        ep.btl.put(ep, dst[::-1].copy(), peer_key)
        w.barrier()
        chk = np.zeros(1024, np.uint8)
        ep.btl.get(ep, chk, peer_key)
        assert chk[0] == 255 and chk[-1] == 0, chk
        w.barrier()
        ep.btl.release_src(key)
        print(f"RMA OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script)
    assert r.stdout.count("RMA OK") == 2, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr


def test_multirail_striping_sm_plus_tcp(tmp_path):
    """Large RNDV streams stripe bandwidth-weighted across every rail
    that reaches the peer (bml_r2 multi-BTL striping): same-host ranks
    have sm AND tcp, and the FRAG stream must use them in proportion."""
    script = tmp_path / "stripe.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.mca.bml import resolve_bml
        from ompi_tpu.runtime import init as rt, spc

        w = ompi_tpu.init()
        bml = resolve_bml(rt.get_world_if_initialized().pml)
        eps = bml.endpoints(1 - w.rank)
        assert [e.btl.name for e in eps] == ["sm", "tcp"], eps
        # comparable rails: with sm's default 100x bandwidth edge the
        # finish-time-greedy schedule CORRECTLY starves tcp; equalize so
        # proportionality itself is what gets tested
        sm, tcp = eps[0].btl, eps[1].btl
        sm.bandwidth = tcp.bandwidth = 100
        carried = {"sm": 0, "tcp": 0}
        for name, btl in (("sm", sm), ("tcp", tcp)):
            orig = btl.send
            def wrapped(ep, frag, _o=orig, _n=name):
                if frag.kind == "frag":
                    carried[_n] += 1
                return _o(ep, frag)
            btl.send = wrapped
        n = (4 << 20) // 8
        if w.rank == 0:
            w.send(np.arange(n, dtype=np.float64), dest=1, tag=5)
            assert spc.read("striped_msgs") >= 1, "stream never striped"
            assert carried["sm"] >= 1 and carried["tcp"] >= 1, carried
            print(f"STRIPE SEND OK {carried}", flush=True)
        else:
            r = np.empty(n, np.float64)
            w.recv(r, source=0, tag=5)
            assert r[0] == 0 and r[-1] == n - 1 and r[n // 3] == n // 3
            print("STRIPE RECV OK", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script, extra=("--mca", "pml_ob1_rget_limit", "0",
                                  "--mca", "pml_ob1_stripe_min", "1m"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "STRIPE SEND OK" in r.stdout and "STRIPE RECV OK" in r.stdout


def test_tcp_multilink(tmp_path):
    """btl_tcp_links > 1: several connections per peer, frames striped
    round-robin; pml seq reordering reassembles across links."""
    script = tmp_path / "links.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.mca.bml import resolve_bml
        from ompi_tpu.runtime import init as rt

        w = ompi_tpu.init()
        peer = 1 - w.rank
        n = (2 << 20) // 8
        if w.rank == 0:
            for it in range(3):
                w.send(np.arange(n, dtype=np.float64) + it, dest=1, tag=it)
        else:
            for it in range(3):
                r = np.empty(n, np.float64)
                w.recv(r, source=0, tag=it)
                assert r[0] == it and r[-1] == n - 1 + it, (it, r)
        bml = resolve_bml(rt.get_world_if_initialized().pml)
        tcp = next(b for b in bml.btls if b.name == "tcp")
        links = tcp._by_rank.get(peer, [])
        assert len(links) >= 3, f"expected >=3 links, got {len(links)}"
        print(f"LINKS OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script, extra=("--fake-nodes", "2",
                                  "--mca", "btl_tcp_links", "3",
                                  "--mca", "pml_ob1_rget_limit", "0"))
    assert r.stdout.count("LINKS OK") == 2, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr
