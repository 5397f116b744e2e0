"""ft/chaos — deterministic fault injection + the self-healing
coord/wire layer it exists to exercise.

Four layers of coverage:

* spec grammar: parse/format round-trip, loud errors on malformed
  specs;
* determinism: the same (seed, rank, site) replays the identical fault
  sequence; per-hook unit semantics (loss faults only on CTL, wire
  faults only on tcp, kill-point counting);
* the self-healing coord client: an injected mid-RPC disconnect —
  including during a fence — heals via idempotent reconnect-retry
  (fetch_add applied exactly once: the acceptance-pinned regression);
* the armed wire checksum: a corrupted checksummed tcp frame is a
  loud, attributed error, never a silent delivery;
* chaos matrix (tpurun): drop/delay/dup/corrupt x 3 seeds over the
  host-collective fuzz — every job completes or fails loudly, never
  hangs; a `slow`-lane soak widens to reset/kill across 8 seeds.
"""
import os
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from ompi_tpu.ft import chaos

import launch

REPO = Path(__file__).resolve().parent.parent
HOSTCOLL = Path(__file__).resolve().parent / "fuzz_hostcoll_worker.py"


@pytest.fixture(autouse=True)
def _disarm():
    yield
    chaos.uninstall()


# ------------------------------------------------------------------ spec

def test_spec_parse_format_roundtrip():
    spec = "drop:p=0.01;delay:ms=5,p=0.05;kill:rank=2,step=7"
    rules = chaos.parse_spec(spec)
    assert [r["fault"] for r in rules] == ["drop", "delay", "kill"]
    assert rules[0]["p"] == 0.01
    assert rules[1]["ms"] == 5.0 and rules[1]["p"] == 0.05
    assert rules[2]["rank"] == 2 and rules[2]["step"] == 7
    # round trip: format -> parse is the identity on the rule list
    assert chaos.parse_spec(chaos.format_spec(rules)) == rules
    # whitespace and empty rules are tolerated
    assert chaos.parse_spec(" drop:p=0.5 ;; ") == [
        {"fault": "drop", "p": 0.5}]


def test_spec_errors_are_loud():
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("explode:p=1")          # unknown fault
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("drop:ms=2")            # param not allowed
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("delay:ms=abc")         # unparsable value
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("drop:p")               # missing '='
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("kill:rank=2")          # kill with no trigger


# ----------------------------------------------------------- determinism

def _decision_trace(seed, rank, events=200):
    eng = chaos._Engine(chaos.parse_spec(
        "drop:p=0.3;delay:p=0.2,ms=1;corrupt:p=0.1"), seed, rank)
    out = []
    for _ in range(events):
        r = eng.match(eng.wire_rules, "tcp:send")
        out.append(None if r is None else r["fault"])
    return out


def test_same_seed_identical_fault_sequence():
    a = _decision_trace(11, 0)
    b = _decision_trace(11, 0)
    assert a == b
    assert any(x is not None for x in a)         # faults actually fire
    # a different seed (or rank, or site) is a different stream
    assert a != _decision_trace(12, 0)
    assert a != _decision_trace(11, 1)


def test_n_cap_limits_rule_firings():
    eng = chaos._Engine(chaos.parse_spec("drop:p=1,n=3"), 0, 0)
    fired = [eng.match(eng.wire_rules, "s") for _ in range(10)]
    assert [r is not None for r in fired] == [True] * 3 + [False] * 7


def test_inapplicable_events_do_not_consume_caps():
    """A capped tcp-only fault offered first to sm events must still
    fire on the first tcp event — inapplicable events never burn the
    n= budget (the review-pass finding)."""
    chaos.install_spec("reset:p=1,n=1", rank=0)
    for _ in range(5):
        assert chaos.wire_send("sm", False) is None   # inapplicable
    assert chaos.wire_send("tcp", False)["fault"] == "reset"
    assert chaos.wire_send("tcp", False) is None      # cap spent NOW


# ------------------------------------------------------------- per-hook

def test_wire_hook_semantics():
    chaos.install_spec("drop:p=1", rank=0)
    # loss faults only touch best-effort CTL traffic
    assert chaos.wire_send("tcp", True)["fault"] == "drop"
    assert chaos.wire_send("tcp", False) is None
    chaos.install_spec("reset:p=1", rank=0)
    # reset is a tcp send-side fault only
    assert chaos.wire_send("tcp", False)["fault"] == "reset"
    assert chaos.wire_send("sm", False) is None
    assert chaos.wire_recv("tcp", False) is None
    chaos.install_spec("corrupt:p=1", rank=0)
    assert chaos.wire_recv("tcp", False)["fault"] == "corrupt"
    assert chaos.wire_send("sm", True) is None   # sm is host RAM


def test_kill_point_count_and_step(monkeypatch):
    killed = []
    monkeypatch.setattr(chaos, "_exit",
                        lambda code: killed.append(code))
    chaos.install_spec("kill:rank=0,site=agree_prepare,count=2", rank=0)
    chaos.kill_point("agree_prepare")
    chaos.kill_point("agree_prepare")
    assert not killed                            # 2 hits permitted
    chaos.kill_point("agree_prepare")
    assert killed == [chaos.KILL_EXIT_CODE]      # dies on the 3rd
    killed.clear()
    chaos.install_spec("kill:rank=0,step=7", rank=0)
    for s in range(7):
        chaos.kill_point("step", n=s)
    assert not killed
    chaos.kill_point("step", n=7)
    assert killed == [chaos.KILL_EXIT_CODE]
    killed.clear()
    # a rank-scoped schedule never fires on another rank
    chaos.install_spec("kill:rank=3,step=1", rank=0)
    chaos.kill_point("step", n=1)
    assert not killed


def test_rank_scoped_wire_and_pace_rules():
    """A fault carrying ``rank=`` arms only on that rank (the
    designed-straggler scoping otpu_analyze's acceptance run uses);
    a ``delay`` carrying ``site=`` moves off the wire onto the named
    chaos.pace point."""
    # rank-scoped wire rule: fires on its rank only
    chaos.install_spec("delay:ms=1,p=1,rank=2", rank=2)
    assert chaos.wire_send("tcp", False)["fault"] == "delay"
    chaos.install_spec("delay:ms=1,p=1,rank=2", rank=0)
    assert chaos.wire_send("tcp", False) is None
    # site-scoped delay: never on the wire, fires at its pace point
    chaos.install_spec("delay:ms=1,p=1,rank=0,site=step", rank=0)
    assert chaos.wire_send("tcp", False) is None
    t0 = __import__("time").perf_counter()
    chaos.pace("step")
    assert __import__("time").perf_counter() - t0 >= 0.8e-3
    chaos.pace("other_site")                     # wrong site: no sleep
    # spec round-trips with the new params
    rules = chaos.parse_spec("delay:ms=8,p=1,rank=2,site=step")
    assert chaos.parse_spec(chaos.format_spec(rules)) == rules
    # the fault log recorded the pace injection (flight-recorder tail)
    assert any(f == "delay" and s == "pace:step"
               for _t, f, s in chaos.event_log())


def test_chaos_off_hooks_are_inert():
    assert chaos.enabled is False
    assert chaos.wire_send("tcp", True) is None
    assert chaos.wire_recv("sm", True) is None
    assert chaos.coord_stall("put") is None
    assert chaos.coord_disconnect("put") is False
    chaos.kill_point("step", n=0)                # no engine: no-op


# ------------------------------------------- self-healing coord client

def _server(n=2):
    from ompi_tpu.rte.coord import CoordServer

    srv = CoordServer(n)
    os.environ["OTPU_COORD"] = f"{srv.addr[0]}:{srv.addr[1]}"
    return srv


def test_coord_fetch_add_exactly_once_across_disconnect():
    """THE idempotent-retry pin: a mid-RPC disconnect (reply lost after
    the server applied the op) must not double-apply on retry —
    fetch_add is the op where a replay would be visible."""
    from ompi_tpu.rte.coord import CoordClient

    srv = _server()
    try:
        c = CoordClient(retries=8)
        chaos.install_spec("disconnect:n=2", rank=0)
        assert c.fetch_add(-1, "ctr", 1) == 0    # injected reset, healed
        assert c.fetch_add(-1, "ctr", 1) == 1    # applied exactly once
        chaos.uninstall()
        assert c.fetch_add(-1, "ctr", 1) == 2
        c.close()
    finally:
        srv.close()


def test_coord_fence_survives_mid_rpc_disconnect():
    """Acceptance pin: a fence interrupted by a client-side reset
    completes via idempotent retry against the reconnected socket —
    the retried arrival is absorbed (set-idempotent) or replayed from
    the server's cache, never double-counted or lost."""
    from ompi_tpu.rte.coord import CoordClient

    srv = _server(2)
    try:
        a = CoordClient(retries=8)
        b = CoordClient(retries=8)
        chaos.install_spec("disconnect:n=1", rank=0)
        done = []
        t1 = threading.Thread(
            target=lambda: (a.fence("F", rank=0), done.append(0)))
        t2 = threading.Thread(
            target=lambda: (b.fence("F", rank=1), done.append(1)))
        t1.start()
        t2.start()
        t1.join(30)
        t2.join(30)
        assert sorted(done) == [0, 1], done
        # the healed client keeps working on the reconnected socket
        a.put(0, "k", "v")
        assert b.get(0, "k") == "v"
        a.close()
        b.close()
    finally:
        srv.close()


def test_coord_rpc_timeout_is_loud_and_client_stays_usable():
    """An RPC that expires (stuck fence) fails with the loud
    otpu_coord_rpc_timeout error AND closes the socket — the next RPC
    on the same client reconnects instead of queueing behind the stuck
    op or mis-reading its stale reply (the review-pass finding)."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.rte.coord import CoordClient

    srv = _server(2)
    var = registry.lookup("otpu_coord_rpc_timeout")
    old = var.value
    var.set(1.0)
    try:
        c = CoordClient(retries=2)
        with pytest.raises(RuntimeError, match="timed out"):
            # expects rank 1 too: blocks server-side past the timeout
            c.fence("stuck", rank=0, expect=[0, 1])
        # the client healed: fresh socket, ordinary RPCs work
        c.put(0, "k", "v")
        assert c.get(0, "k") == "v"
        c.close()
    finally:
        var.set(old)
        srv.close()


def test_coord_timeout_on_non_fence_op_retries_exactly_once():
    """The fleet-soak shrink-path flake: a recovery-path coord RPC
    (pset/KV traffic, NOT a fence) that expires because the coord was
    too loaded to answer in time must retry within otpu_coord_retry_max
    instead of surfacing as a survivor exception — and the replay cache
    must keep the retried op exactly-once.  Induced via the chaos coord
    hooks: the server consults the same ``stall`` rules, so firing 2
    stalls the server past a shrunken otpu_coord_rpc_timeout while the
    op is in flight (firing 1 is consumed by the harmless client-side
    pre-send hook)."""
    from ompi_tpu.base.var import registry
    from ompi_tpu.rte.coord import CoordClient
    from ompi_tpu.runtime import spc

    srv = _server()
    var = registry.lookup("otpu_coord_rpc_timeout")
    old = var.value
    var.set(0.5)
    try:
        spc.init()
        before = spc.read("coord_rpc_retries")
        c = CoordClient(retries=4)
        chaos.install_spec("stall:p=1,ms=900,n=2", rank=0)
        # firing 1: client pre-send (a delay, harmless); firing 2: the
        # SERVER stalls past the timeout -> client times out mid-RPC,
        # retries, and the retry is adopted against the in-flight
        # original (exactly-once: the counter advances by 1 total)
        assert c.fetch_add(-1, "ctr", 1) == 0
        assert spc.read("coord_rpc_retries") >= before + 1
        chaos.uninstall()
        assert c.fetch_add(-1, "ctr", 1) == 1    # applied exactly once
        c.close()
    finally:
        var.set(old)
        srv.close()


def test_coord_malformed_request_is_loud_not_stuck():
    """A request whose server-side handling raises (malformed /
    version-skewed frame) must come back as a loud error response, not
    strand its in-flight claim for a retry to spin on forever (the
    review-pass finding)."""
    from ompi_tpu.rte.coord import CoordClient

    srv = _server()
    try:
        c = CoordClient(retries=2)
        with pytest.raises(RuntimeError, match="server error"):
            c._rpc(op="get")          # missing rank/key -> KeyError
        # the claim was released and the client keeps working
        c.put(0, "k", "v")
        assert c.get(0, "k") == "v"
        c.close()
    finally:
        srv.close()


def test_coord_stall_injection_counts():
    from ompi_tpu.rte.coord import CoordClient
    from ompi_tpu.runtime import spc

    srv = _server()
    try:
        spc.init()
        before = spc.read("chaos_stall")
        c = CoordClient(retries=2)
        chaos.install_spec("stall:p=1,ms=1,n=3", rank=0)
        for _ in range(5):
            c.put(0, "k", 1)
        assert spc.read("chaos_stall") == before + 3
        c.close()
    finally:
        srv.close()


# --------------------------------------------------- wire checksum (tcp)

def _mk_conn():
    import socket

    from ompi_tpu.mca.btl import tcp as tcp_mod

    s1, s2 = socket.socketpair()
    conn = tcp_mod._Conn(s1)
    conn.rank = 9
    return tcp_mod, conn, (s1, s2)


def _ck_frame(tcp_mod, payload: bytes) -> bytearray:
    """A checksummed fast-header frame, built the way send() builds it."""
    import struct
    import zlib

    from ompi_tpu.mca.btl.base import MATCH, Frag

    hdr = tcp_mod._fast_header(Frag(0, 9, 0, 5, 1, MATCH, payload))
    crc = zlib.crc32(payload, zlib.crc32(hdr))
    frame_len = 1 + tcp_mod._CKSUM.size + len(hdr) + len(payload)
    return bytearray(
        tcp_mod._LEN.pack(frame_len)
        + bytes((tcp_mod._H_FAST + tcp_mod._H_CK_BASE,))
        + tcp_mod._CKSUM.pack(crc) + hdr + payload)


def test_checksummed_frame_verifies_and_delivers():
    tcp_mod, conn, socks = _mk_conn()
    btl = tcp_mod.TcpBtl()
    got = []
    btl.set_recv_callback(got.append)
    try:
        frame = _ck_frame(tcp_mod, b"hello-kv")
        n = btl._on_bytes(conn, memoryview(frame))
        assert n == 1 and bytes(got[0].data) == b"hello-kv"
    finally:
        for s in socks:
            s.close()


def test_corrupted_frame_is_loud_and_attributed(capsys):
    from ompi_tpu.runtime import sanitizer, spc

    spc.init()
    before = spc.read("wire_cksum_fail")
    tcp_mod, conn, socks = _mk_conn()
    btl = tcp_mod.TcpBtl()
    btl.set_recv_callback(lambda frag: None)
    try:
        frame = _ck_frame(tcp_mod, b"hello-kv")
        frame[-1] ^= 0x40                        # wire bit rot
        with pytest.raises(sanitizer.SanitizeError) as ei:
            btl._on_bytes(conn, memoryview(frame))
        assert "rank 9" in str(ei.value)         # attributed
        assert spc.read("wire_cksum_fail") == before + 1
        err = capsys.readouterr().err
        assert "corrupted on the wire" in err    # show_help fired
    finally:
        for s in socks:
            s.close()


def test_unchecksummed_frame_still_parses():
    """Mixed arming interoperates: a plain (htype<2) frame from an
    unarmed sender parses normally on an armed receiver."""
    tcp_mod, conn, socks = _mk_conn()
    btl = tcp_mod.TcpBtl()
    got = []
    btl.set_recv_callback(got.append)
    try:
        from ompi_tpu.mca.btl.base import MATCH, Frag

        payload = b"plain"
        hdr = tcp_mod._fast_header(Frag(0, 9, 0, 5, 1, MATCH, payload))
        frame = (tcp_mod._LEN.pack(1 + len(hdr) + len(payload))
                 + bytes((tcp_mod._H_FAST,)) + hdr + payload)
        n = btl._on_bytes(conn, memoryview(bytearray(frame)))
        assert n == 1 and bytes(got[0].data) == b"plain"
    finally:
        for s in socks:
            s.close()


# ------------------------------------------------- chaos matrix (tpurun)

def _run_matrix_job(spec: str, seed: int, timeout=150):
    return launch.tpurun(
        2, HOSTCOLL, timeout=timeout,
        extra=("--mca", "otpu_chaos_spec", spec,
               "--mca", "otpu_chaos_seed", str(seed),
               # detector on: CTL heartbeat traffic gives the loss faults
               # something to chew on; generous envelope so injected
               # delays don't read as deaths
               "--mca", "ft_detector", "true",
               "--mca", "ft_detector_period", "0.3",
               "--mca", "ft_detector_timeout", "6.0",
               "--mca", "ft_detector_startup_grace", "6.0"),
        env=dict(HF_SEED=str(seed), HF_ITERS="4"))


_MATRIX = ["drop:p=0.05", "delay:ms=2,p=0.2", "dup:p=0.2",
           "corrupt:p=0.02"]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("spec", _MATRIX)
def test_chaos_matrix_completes_or_fails_loudly(spec, seed):
    """Every (fault, seed) cell either completes the randomized
    host-collective shake or dies LOUDLY (attributed corruption error /
    injected-fault marker) — never a hang (subprocess timeout) and
    never silent corruption (the worker checks every result against
    numpy)."""
    r = _run_matrix_job(spec, seed)
    out = r.stdout + r.stderr
    if r.returncode == 0:
        assert "randomized iterations OK" in out
    else:
        assert ("corrupted on the wire" in out
                or "crc32" in out
                or "[chaos]" in out
                or "chaos" in out), (
            f"{spec} seed {seed}: failed WITHOUT a loud attributed "
            f"error\n{out[-3000:]}")


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(8)))
def test_chaos_soak(seed):
    """The full-menu soak: drop/delay/dup/corrupt/reset/kill across 8
    seeds, recovery mode on.  Zero hangs; every fault heals or fails
    loudly."""
    spec = ("drop:p=0.02;delay:ms=1,p=0.05;dup:p=0.05;"
            "corrupt:p=0.005;reset:p=0.01;kill:rank=1,after=4.0")
    env = dict(os.environ, JAX_PLATFORMS="cpu", HF_SEED=str(seed),
               HF_ITERS="12")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
           "--enable-recovery",
           "--mca", "otpu_chaos_spec", spec,
           "--mca", "otpu_chaos_seed", str(seed),
           "--mca", "ft_detector", "true",
           "--mca", "ft_detector_period", "0.3",
           "--mca", "ft_detector_timeout", "6.0",
           "--mca", "ft_detector_startup_grace", "6.0",
           sys.executable, str(HOSTCOLL)]
    r = launch.run(cmd, 240, env)
    out = r.stdout + r.stderr
    if r.returncode != 0:
        assert ("corrupted on the wire" in out or "crc32" in out
                or "[chaos]" in out or "chaos" in out
                or "failed" in out), (
            f"soak seed {seed}: failed silently\n{out[-3000:]}")
