"""Seeded replay fuzz for vprotocol/pessimist (channel event clocks).

Each seed drives a randomized piecewise-deterministic exchange program
(tests/fuzz_replay_worker.py): per-round single- or dual-comm sends
with seed-chosen comms/tags and plan-chosen consumption order, and a
seed-derived kill point for rank 1 (after its sends, or between its two
recvs of a dual round).  Phase A crashes mid-program under full
sender-based logging; phase B replays every rank from the logs and must
reproduce the failure-free recurrence (numpy simulation) to 1e-12 —
any payload mis-pairing across the interleaved channels corrupts the
asymmetric fold immediately.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from launch import tpurun

REPO = Path(__file__).resolve().parent.parent
WORKER = Path(__file__).resolve().parent / "fuzz_replay_worker.py"

ROUNDS = 6
SEEDS = [3, 14, 27, 42]


def _mod():
    spec = importlib.util.spec_from_file_location("fuzz_replay_worker",
                                                  WORKER)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _run(env_extra, mca=(), timeout=180):
    extra = ["--enable-recovery"]
    for k, v in mca:
        extra += ["--mca", k, v]
    return tpurun(2, WORKER, timeout=timeout, extra=extra, env=env_extra)


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_replay_reproduces_recurrence(seed, tmp_path):
    m = _mod()
    _, kill_round, kill_pos = m.build_plan(seed, ROUNDS)
    logdir = tmp_path / "logs"

    # phase A: crash at the seed-derived point under full logging
    ra = _run({"VPF_SEED": str(seed), "VPF_ROUNDS": str(ROUNDS),
               "VPF_NITER": str(kill_round + 1), "VPF_DIE": "1",
               "VPF_OUT": str(tmp_path / "a")},
              mca=[("vprotocol_pessimist_log", str(logdir)),
                   ("vprotocol_pessimist_log_payloads", "1"),
                   ("ft_detector", "true"),
                   ("ft_detector_period", "0.2"),
                   ("ft_detector_timeout", "1.5")])
    assert not (tmp_path / "a.1.npy").exists(), (
        f"seed {seed}: rank 1 survived its {kill_pos} kill at round "
        f"{kill_round}\n{ra.stdout}{ra.stderr}")

    # phase B: full program, every rank replayed from the logs
    rb = _run({"VPF_SEED": str(seed), "VPF_ROUNDS": str(ROUNDS),
               "VPF_NITER": str(ROUNDS), "VPF_DIE": "0",
               "VPF_OUT": str(tmp_path / "b")},
              mca=[("vprotocol_pessimist_replay", str(logdir))])
    assert rb.returncode == 0, (seed, rb.stdout + rb.stderr)
    assert rb.stdout.count("VPF DONE") == 2, (seed, rb.stdout)

    want = m.simulate(seed, ROUNDS, ROUNDS)
    for r in range(2):
        got = np.load(tmp_path / f"b.{r}.npy")
        np.testing.assert_allclose(got, want[r], rtol=1e-12, err_msg=(
            f"seed {seed} rank {r}: replay diverged from the "
            f"failure-free recurrence (kill was {kill_pos}@"
            f"{kill_round})"))
