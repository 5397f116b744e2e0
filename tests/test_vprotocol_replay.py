"""vprotocol/pessimist replay — a restarted rank is re-driven from the
message logs to its pre-failure state, then continues live with peers
(``ompi/mca/vprotocol/pessimist`` re-delivery semantics).

Scenario: 3 ranks run a deterministic ring recurrence with full
sender-based logging; rank 1 dies MID-iteration (after its sends, before
its recvs).  A second job replays every rank from the logs: suppressed
sends where delivery is proven by the receiver's log, a live re-send for
the in-flight message the dead rank never received, pinned-source recvs
satisfied from the senders' logged payloads — then the log runs dry and
live execution finishes the remaining iterations.  Final states must
match the failure-free recurrence computed locally.
"""
import os
import textwrap

import numpy as np

import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NITER_TOTAL = 5
DIE_ROUND = 2   # rank 1 dies in round 2 after sending, before receiving

_PROGRAM = """
import os, sys
import numpy as np
import ompi_tpu

niter = int(os.environ["VP_NITER"])
die = os.environ.get("VP_DIE", "") == "1"
w = ompi_tpu.init()
n, r = w.size, w.rank
state = np.full(4, float(r + 1), np.float64)
for it in range(niter):
    req = w.isend(state.copy(), dest=(r + 1) % n, tag=7)
    if die and r == 1 and it == {die_round}:
        os._exit(9)     # mid-iteration: sent but never received
    inbuf = np.empty_like(state)
    w.recv(inbuf, source=(r - 1) % n, tag=7)
    req.wait()
    state = 0.5 * state + 0.5 * inbuf + float(it)
np.save(os.environ["VP_OUT"] + f".{{r}}.npy", state)
print(f"DONE {{r}} " + " ".join(f"{{x:.6f}}" for x in state), flush=True)
ompi_tpu.finalize()
"""


def _run(n, script, env_extra, mca=(), timeout=180):
    extra = ["--enable-recovery"]
    for k, v in mca:
        extra += ["--mca", k, v]
    return launch.tpurun(n, script, timeout=timeout, extra=extra,
                         env=env_extra)


def _expected(niter, n=3):
    states = [np.full(4, float(r + 1), np.float64) for r in range(n)]
    for it in range(niter):
        prev = [s.copy() for s in states]
        for r in range(n):
            states[r] = 0.5 * prev[r] + 0.5 * prev[(r - 1) % n] + float(it)
    return states


def test_replay_after_midround_death(tmp_path):
    logdir = tmp_path / "logs"
    prog = tmp_path / "prog.py"
    prog.write_text(textwrap.dedent(
        _PROGRAM.format(die_round=DIE_ROUND)))

    # phase A: run up to the crash boundary; rank 1 dies mid-round
    ra = _run(3, prog,
              {"VP_NITER": str(DIE_ROUND + 1), "VP_DIE": "1",
               "VP_OUT": str(tmp_path / "a")},
              mca=[("vprotocol_pessimist_log", str(logdir)),
                   ("vprotocol_pessimist_log_payloads", "1"),
                   ("ft_detector", "true"),
                   ("ft_detector_period", "0.2"),
                   ("ft_detector_timeout", "1.5")])
    assert ra.stdout.count("DONE") == 2, ra.stdout + ra.stderr
    assert not (tmp_path / f"a.1.npy").exists()   # rank 1 really died
    for r in (0, 2):
        assert (tmp_path / f"a.{r}.npy").exists(), ra.stdout + ra.stderr

    # phase B: "respawn" — every rank re-driven from the logs, the dead
    # rank catching the in-flight re-send live, then all finish the
    # remaining rounds live
    rb = _run(3, prog,
              {"VP_NITER": str(NITER_TOTAL), "VP_DIE": "0",
               "VP_OUT": str(tmp_path / "b")},
              mca=[("vprotocol_pessimist_replay", str(logdir))])
    assert rb.returncode == 0, rb.stdout + rb.stderr
    assert rb.stdout.count("DONE") == 3, rb.stdout + rb.stderr

    want = _expected(NITER_TOTAL)
    for r in range(3):
        got = np.load(tmp_path / f"b.{r}.npy")
        np.testing.assert_allclose(got, want[r], rtol=1e-12, err_msg=(
            f"rank {r} state diverged after replay"))


_TWO_COMM_PROGRAM = """
import os, sys
import numpy as np
import ompi_tpu

niter = int(os.environ["VP_NITER"])
die = os.environ.get("VP_DIE", "") == "1"
w = ompi_tpu.init()
d = w.dup()
r = w.rank
peer = 1 - r
state = np.full(4, float(r + 1), np.float64)
for it in range(niter):
    a = 0.5 * state + float(it)        # the w-channel payload
    b = 0.25 * state - float(it)       # the d-channel payload
    if r == 0:
        q1 = w.isend(a, dest=peer, tag=5)
        q2 = d.isend(b, dest=peer, tag=5)
        inA = np.empty_like(state); inB = np.empty_like(state)
        # peer emitted d-then-w: consume w-then-d (cross-channel
        # interleave both directions)
        w.recv(inA, source=peer, tag=5)
        d.recv(inB, source=peer, tag=5)
    else:
        q2 = d.isend(b, dest=peer, tag=5)
        q1 = w.isend(a, dest=peer, tag=5)
        inA = np.empty_like(state); inB = np.empty_like(state)
        # peer emitted w-then-d: consume d-then-w
        d.recv(inB, source=peer, tag=5)
        if die and r == 1 and it == {die_round}:
            os._exit(9)   # w message of this round in flight
        w.recv(inA, source=peer, tag=5)
    q1.wait(); q2.wait()
    # asymmetric in A/B: a swapped pairing corrupts the state
    state = 0.3 * state + 0.6 * inA - 0.2 * inB + float(it)
np.save(os.environ["VP_OUT"] + f".{{r}}.npy", state)
print(f"DONE {{r}}", flush=True)
ompi_tpu.finalize()
"""


def _expected_two_comm(niter, n=2):
    states = [np.full(4, float(r + 1), np.float64) for r in range(n)]
    for it in range(niter):
        prev = [s.copy() for s in states]
        for r in range(n):
            in_a = 0.5 * prev[1 - r] + float(it)
            in_b = 0.25 * prev[1 - r] - float(it)
            states[r] = (0.3 * prev[r] + 0.6 * in_a - 0.2 * in_b
                         + float(it))
    return states


def test_replay_two_comm_interleaved(tmp_path):
    """Event-clock pairing (``vprotocol_pessimist_event.h`` analog):
    concurrent traffic on TWO communicators between the same pair, with
    each side consuming channels in the OPPOSITE order of the peer's
    emission — per-(cid,tag) channel clocks must pair every payload
    exactly; global send-order pairing would swap the A/B payloads and
    corrupt the recurrence.  Rank 1 dies between its two recvs, leaving
    the w-channel message of that round in flight."""
    logdir = tmp_path / "logs"
    prog = tmp_path / "prog2.py"
    prog.write_text(textwrap.dedent(
        _TWO_COMM_PROGRAM.format(die_round=DIE_ROUND)))

    ra = _run(2, prog,
              {"VP_NITER": str(DIE_ROUND + 1), "VP_DIE": "1",
               "VP_OUT": str(tmp_path / "a")},
              mca=[("vprotocol_pessimist_log", str(logdir)),
                   ("vprotocol_pessimist_log_payloads", "1"),
                   ("ft_detector", "true"),
                   ("ft_detector_period", "0.2"),
                   ("ft_detector_timeout", "1.5")])
    assert ra.stdout.count("DONE") == 1, ra.stdout + ra.stderr
    assert not (tmp_path / "a.1.npy").exists()

    rb = _run(2, prog,
              {"VP_NITER": str(NITER_TOTAL), "VP_DIE": "0",
               "VP_OUT": str(tmp_path / "b")},
              mca=[("vprotocol_pessimist_replay", str(logdir))])
    assert rb.returncode == 0, rb.stdout + rb.stderr
    assert rb.stdout.count("DONE") == 2, rb.stdout + rb.stderr

    want = _expected_two_comm(NITER_TOTAL)
    for r in range(2):
        got = np.load(tmp_path / f"b.{r}.npy")
        np.testing.assert_allclose(got, want[r], rtol=1e-12, err_msg=(
            f"rank {r} state diverged after two-comm replay"))


def test_replay_divergence_detected(tmp_path):
    """A re-execution that does not match the log must fail loudly, not
    silently corrupt recovery (envelope verification)."""
    logdir = tmp_path / "logs"
    prog = tmp_path / "prog.py"
    prog.write_text(textwrap.dedent(
        _PROGRAM.format(die_round=DIE_ROUND)))
    ra = _run(3, prog,
              {"VP_NITER": "2", "VP_DIE": "0",
               "VP_OUT": str(tmp_path / "a")},
              mca=[("vprotocol_pessimist_log", str(logdir)),
                   ("vprotocol_pessimist_log_payloads", "1")])
    assert ra.returncode == 0, ra.stdout + ra.stderr

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        try:
            # logged program used tag=7; this diverges
            w.send(np.zeros(4), dest=(w.rank + 1) % w.size, tag=99)
        except Exception as e:
            assert type(e).__name__ == "ReplayDivergence", e
            print(f"DIVERGED {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    rb = _run(3, bad, {"VP_OUT": str(tmp_path / "x")},
              mca=[("vprotocol_pessimist_replay", str(logdir))])
    assert rb.stdout.count("DIVERGED") == 3, rb.stdout + rb.stderr
